"""Share of the traced window in which the chip waited on the transfers
of the timing update's host callbacks: the union of the operations named
in ``chipbench/fused_spans.py``'s ``CALLBACK_OPS``, averaged over the
chips used.  The idle share counts this wait as busy."""
from chipbench import fused_spans


def read(ctx):
    return fused_spans.callback_wait_share(ctx.trace)
