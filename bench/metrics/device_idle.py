"""device_idle: share of the traced window in which no operation ran on a
device (1 - the union of its operation intervals over the window), mean
over the cell's chips."""
from bench import devtrace


def read(ctx):
    if ctx.trace is None:
        return None
    return 100.0 * devtrace.device_idle(ctx.trace)
