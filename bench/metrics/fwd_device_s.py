"""fwd_device_s: device seconds a step in the forward pass (JAX's
``jvp(`` marker), mean over the cell's chips, by ``phases.reader``."""
from bench import phases

read = phases.reader("forward")
