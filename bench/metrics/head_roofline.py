"""Logits head kernel's share of its roofline, in %: the least time of
every call in the traced window over the kernel's device time (the
family's ``kernel_calls(...)["head"]``)."""
from bench import roofline


def read(ctx):
    if ctx.trace is None:
        return None
    pk = roofline.peaks(ctx.device_kind)
    kernels = ctx.family.kernel_calls(ctx.conf, ctx.rows,
                                      ctx.table_itemsize).get("head", {})
    least = spent = 0.0
    for kernel, each in kernels.items():
        calls, secs = ctx.trace["ops"].get(kernel, (0, 0.0))
        per = [roofline.least_time(*call, pk) for call in each]
        least += 100.0 * calls * sum(per) / len(per)
        spent += secs
    return least / spent if spent else None
