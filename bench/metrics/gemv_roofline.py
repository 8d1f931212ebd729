"""Stacked-GEMV kernels' share of their roofline, in %: the least time of
every call in the traced window over the kernels' device time.  Calls are
counted per kernel name; each name's calls are charged the mean least time
of the projections it runs (``roofline.GEMV_KERNELS``)."""
from bench import roofline


def read(ctx):
    if ctx.trace is None:
        return None
    pk = roofline.peaks(ctx.device_kind)
    least = spent = 0.0
    for kernel, names in roofline.GEMV_KERNELS.items():
        calls, secs = ctx.trace["ops"].get(kernel, (0, 0.0))
        per = [roofline.least_time(*roofline.gemv_call(
            ctx.conf, n, ctx.rows, ctx.table_itemsize), pk) for n in names]
        least += calls * sum(per) / len(per)
        spent += secs
    return 100.0 * least / spent if spent else None
