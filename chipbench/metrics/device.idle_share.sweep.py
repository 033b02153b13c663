"""Share of the traced window in which no operation ran on the chip
(profiler trace: 1 - union of device operation intervals / window).  The
chip counts its waits on the host callbacks as busy: see
``device.callback_wait_share.sweep``."""


def read(ctx):
    v = ctx.trace.idle_share
    return None if v is None else 100.0 * v
