"""Share of its roofline the ``ri_histogram`` kernel reached: the least
time the chip needs to bin the reuse intervals of one training's trace
(``chipbench/roofline.py``), times the kernel's calls in the window, over
the kernel's device time in the trace."""
from chipbench import roofline

# the kernel's operations take the name of its jitted wrapper
PATTERN = "histogram"


def read(ctx):
    calls = ctx.trace.kernel_calls(PATTERN)
    if not calls:
        return None
    intervals = ctx.work()["ri_intervals_per_call"]
    if intervals <= 0:
        raise ValueError(f"{PATTERN} ran {len(calls)} times, but the "
                         "trainings bin no reuse interval")
    ops, byts = roofline.ri_histogram_work(len(calls) * intervals)
    return roofline.share(ops, byts, sum(s for _, s in calls),
                          ctx.device_kind)[0]
