"""Backend compiles during set-up, persistent-cache fetches included."""


def read(ctx):
    return float(ctx.setup["backend_compiles"])
