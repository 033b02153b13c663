"""Milliseconds per group in the program's ``fused.device_wait`` spans:
blocked on the super-steps' outputs, the host callbacks of the timing
update included (``chipbench/fused_spans.py``)."""
from chipbench import fused_spans


def read(ctx):
    return fused_spans.phase_ms(ctx.trace, "fused.device_wait")
