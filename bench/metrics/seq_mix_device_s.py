"""seq_mix_device_s: device seconds a step in the ``seq_mix`` scope (the
sequence mixer's core), in any phase, mean over the cell's chips, by
``phases.reader``."""
from bench import phases

read = phases.reader("seq_mix")
