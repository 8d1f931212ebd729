"""95th percentile of every gap between consecutive output tokens of one
request inside the window, in ms."""
from bench import window


def read(ctx):
    p = window.percentile(window.gaps(ctx.token_times, ctx.w0, ctx.w1), 95)
    return None if p is None else 1e3 * p
