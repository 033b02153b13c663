"""Milliseconds per training in the program's ``lern.extract`` span: host
sort and padding, the feature program, its read-back and the eligibility
scan (``chipbench/spans.py``)."""
from chipbench import spans


def read(ctx):
    return spans.phase_ms(ctx.trace, "lern.extract")
