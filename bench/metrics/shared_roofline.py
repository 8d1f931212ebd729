"""Shared block's table kernels' share of their roofline, in %: the least
time of the family's ``kernel_calls(...)["shared"]`` (one entry per call
in a step) times the steps in the traced window (the calls of the
family's ``STEP_KERNEL``), over those kernels' device self time under the
decode step's ``shared`` scope."""
from bench import program_trace, roofline


def read(ctx):
    red = program_trace.for_ctx(ctx)
    if ctx.trace is None or not red:
        return None
    kernels = ctx.family.kernel_calls(ctx.conf, ctx.rows,
                                      ctx.table_itemsize).get("shared", {})
    steps = ctx.trace["ops"].get(ctx.family.STEP_KERNEL, (0, 0.0))[0]
    under = red["scope_ops"].get("shared", {})
    spent = sum(under.get(k, 0.0) for k in kernels)
    if not steps or not spent:
        return None
    pk = roofline.peaks(ctx.device_kind)
    least = steps * sum(roofline.least_time(*call, pk)
                        for calls in kernels.values() for call in calls)
    return 100.0 * least / spent
