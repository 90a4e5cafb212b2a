"""remat_device_s: device seconds a step in full remat's second forward
(JAX's ``rematted_computation``), mean over the cell's chips, by
``phases.reader``."""
from bench import phases

read = phases.reader("remat")
