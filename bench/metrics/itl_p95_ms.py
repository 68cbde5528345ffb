"""95th percentile of the gaps between consecutive output tokens of a
request, over every gap that ended inside the window, in ms."""
from bench import driver


def read(ctx):
    w = ctx.window
    v = driver.token_gaps(ctx.records, w.t0, w.t1)
    return 1e3 * driver.percentile(v, 95) if v else None
