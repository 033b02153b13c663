"""Milliseconds per group in the program's ``fused.dispatch`` spans:
tracing, compiling and enqueueing the super-steps
(``chipbench/fused_spans.py``)."""
from chipbench import fused_spans


def read(ctx):
    return fused_spans.phase_ms(ctx.trace, "fused.dispatch")
