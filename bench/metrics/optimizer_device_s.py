"""optimizer_device_s: device seconds a step in the ``optimizer`` scope
(AdamW with its clip), mean over the cell's chips, by ``phases.reader``."""
from bench import phases

read = phases.reader("optimizer")
