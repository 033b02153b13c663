"""Milliseconds per group in the program's ``fused.stage`` spans: staging
of the group's constants and the carry set-up
(``chipbench/fused_spans.py``)."""
from chipbench import fused_spans


def read(ctx):
    return fused_spans.phase_ms(ctx.trace, "fused.stage")
