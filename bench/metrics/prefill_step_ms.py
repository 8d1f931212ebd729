"""Mean host ms of one prefill step (an ``Engine._step`` call inside
``Engine._prefill_into_slot``), from call to committed tokens."""
from bench import window


def read(ctx):
    d = window.spans(ctx.spans, "prefill_step", ctx.w0, ctx.w1)
    return 1e3 * sum(d) / len(d) if d else None
