"""Stacked-GEMV kernels' share of their roofline, in %: the least time of
every call in the traced window over the kernels' device time.  Calls are
counted per kernel name; each name's calls are charged the mean least time
of the calls it runs (the family's ``kernel_calls(...)["gemv"]``)."""
from bench import roofline


def read(ctx):
    if ctx.trace is None:
        return None
    pk = roofline.peaks(ctx.device_kind)
    kernels = ctx.family.kernel_calls(ctx.conf, ctx.rows,
                                      ctx.table_itemsize).get("gemv", {})
    least = spent = 0.0
    for kernel, each in kernels.items():
        calls, secs = ctx.trace["ops"].get(kernel, (0, 0.0))
        per = [roofline.least_time(*call, pk) for call in each]
        least += calls * sum(per) / len(per)
        spent += secs
    return 100.0 * least / spent if spent else None
