"""Shared-pool logits head kernel's share of its roofline, in %."""
from bench import roofline


def read(ctx):
    if ctx.trace is None:
        return None
    calls, secs = ctx.trace["ops"].get(roofline.HEAD_KERNEL, (0, 0.0))
    if not secs:
        return None
    t = roofline.least_time(*roofline.head_call(ctx.conf, ctx.rows, 4),
                            roofline.peaks(ctx.device_kind))
    return 100.0 * calls * t / secs
