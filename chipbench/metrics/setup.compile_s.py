"""Seconds JAX spent tracing, lowering and compiling during set-up (the
union of its reported intervals, persistent-cache fetches included)."""


def read(ctx):
    return ctx.setup["compile_s"]
