"""Median over the requests that arrived in the window of the time from
scheduled arrival to first token (a request still waiting at the window's
end counts at its wait so far)."""
from bench import window


def read(ctx):
    return window.percentile(
        window.ttfts(ctx.arrivals, ctx.token_times, ctx.w0, ctx.w1), 50)
