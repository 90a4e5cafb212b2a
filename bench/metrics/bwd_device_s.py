"""bwd_device_s: device seconds a step in the backward pass (JAX's
``transpose(`` marker), mean over the cell's chips, by ``phases.reader``."""
from bench import phases

read = phases.reader("backward")
