"""Output tokens committed in the window, over the window's seconds."""
from bench import window


def read(ctx):
    return window.tok_s(ctx.token_times, ctx.w0, ctx.w1)
