"""Milliseconds per training in the program's ``lern.assemble`` spans,
summed over the models a training assembles: host annotation and the
model's tables (``chipbench/spans.py``)."""
from chipbench import spans


def read(ctx):
    return spans.phase_ms(ctx.trace, "lern.assemble")
