"""Output tokens served inside the window, over the window, in tokens/s."""
from bench import driver


def read(ctx):
    w = ctx.window
    n = driver.tokens_in(ctx.records, w.t0, w.t1)
    return n / ctx.window_s if n else None
