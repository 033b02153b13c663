"""Milliseconds per training in the program's ``lern.fit`` span: the
segment layout, the k-means programs, any straggler re-dispatch and the
fit's read-back (``chipbench/spans.py``)."""
from chipbench import spans


def read(ctx):
    return spans.phase_ms(ctx.trace, "lern.fit")
