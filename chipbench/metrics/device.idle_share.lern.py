"""Share of the traced window in which no operation ran on the chip
(profiler trace: 1 - union of device operation intervals / window)."""


def read(ctx):
    v = ctx.trace.idle_share
    return None if v is None else 100.0 * v
