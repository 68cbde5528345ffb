"""Seconds from the process's start to the window's: the weights made,
the engine built, both programs compiled or loaded from the cache and
run once, and the mix's warm-up traffic served."""


def read(ctx):
    return ctx.setup_s
