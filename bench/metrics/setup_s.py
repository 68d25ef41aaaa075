"""Process start to the first measured request: imports, flexible
matching, warm-up and compiles."""


def read(ctx):
    return ctx.setup_s
