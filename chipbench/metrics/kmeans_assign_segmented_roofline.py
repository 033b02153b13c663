"""Share of its roofline the ``kmeans_assign_segmented`` kernel reached, over
the calls that assign every point of a fit (its first Lloyd sweeps and
its final assignment): the least time the chip needs for the real points
those calls assign (``chipbench/roofline.py``) over their device time.

The fit re-dispatches the segments still moving after its first sweeps on
a smaller array; which points those hold is not seen from the host, so
those calls are left out of both the work and the time.  A call assigns
every point when its array has the most rows of the window's calls."""
import re

from chipbench import roofline

# the kernel's operations take the name of its jitted wrapper
PATTERN = "assign_segmented"
# ``%assign_segmented.9 = s32[1,106496]{...} custom-call(...)``: one label
# per row of the point array
ROWS = re.compile(r"= s32\[1,(\d+)\]")


def read(ctx):
    calls = ctx.trace.kernel_calls(PATTERN)
    if not calls:
        return None
    rows = []
    for text, _ in calls:
        m = ROWS.search(text)
        if m is None:
            raise ValueError(f"{PATTERN}: no label row in {text[:200]!r}")
        rows.append(int(m.group(1)))
    full = max(rows)
    n = sum(r == full for r in rows)
    t = sum(s for (_, s), r in zip(calls, rows) if r == full)
    work = ctx.work()
    pts = work["kmeans_points_per_call"]
    if pts <= 0 or pts > full:
        raise ValueError(f"{PATTERN} ran {len(calls)} times, but the "
                         f"points per call read {pts} against {full} rows")
    ops, byts = roofline.kmeans_assign_work(
        n * pts, n * work["kmeans_problems_per_call"])
    return roofline.share(ops, byts, t, ctx.device_kind)[0]
