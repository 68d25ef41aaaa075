"""Samples of every answered request over the window: from the first
request's send to the last answer."""


def read(ctx):
    return ctx.samples_done / ctx.window_s if ctx.samples_done else None
