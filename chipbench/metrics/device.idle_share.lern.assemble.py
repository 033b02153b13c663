"""Share of the traced window in which the chip was idle while the host
was inside the program's ``lern.assemble`` span: device 0's idle
intervals, over every gap of the window, intersected with the span's
intervals (``chipbench/spans.py``)."""
from chipbench import spans


def read(ctx):
    return spans.phase_idle_share(ctx.trace, "lern.assemble")
