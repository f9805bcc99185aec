"""Share of the traced training steps in which the chips ran no operation
(profiler trace: 1 - the union of ``XLA Ops`` intervals over the window,
averaged over the cell's chips).  Layer: the driver loop."""


def read(ctx):
    if ctx.kind != "train" or not ctx.trace.devices:
        return None
    return 100.0 * (1.0 - ctx.trace.busy_s / ctx.trace.window_s)
