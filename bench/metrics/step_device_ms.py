"""Device busy ms per decode-path step: the union of device operation time
in the traced window over the steps run in it (the calls of the family's
``STEP_KERNEL``, which runs once a step)."""


def read(ctx):
    if ctx.trace is None:
        return None
    steps = ctx.trace["ops"].get(ctx.family.STEP_KERNEL, (0, 0.0))[0]
    return 1e3 * ctx.trace["busy_s"] / steps if steps else None
