"""Milliseconds per group in the program's ``fused.writeback`` spans: the
per-epoch history and carry sync on the host
(``chipbench/fused_spans.py``)."""
from chipbench import fused_spans


def read(ctx):
    return fused_spans.phase_ms(ctx.trace, "fused.writeback")
