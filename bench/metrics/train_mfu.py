"""train_mfu: model FLOPs of the traced steps over the seconds in which
the device ran an operation, as a share of the chips' bf16 peak
(``peaks.json``).

The FLOPs come from ``flops/<name>.py`` (the configuration's own shapes,
full remat's second forward not counted). The time is the device's busy
time in the traced window (``devtrace.busy_s``), which holds a whole
number of steps: time in which the device waits on the host moves
``device_idle`` and the throughput, not this share.
"""
from bench import devtrace


def read(ctx):
    if ctx.trace is None or not ctx.traced_steps:
        return None
    busy = devtrace.busy_s(ctx.trace)
    if busy <= 0:
        return None
    rate = ctx.model_flops_per_step * ctx.traced_steps / busy
    return 100.0 * rate / (ctx.n_chips * ctx.peak["bf16_flops_per_s"])
