"""Device idle ms per step (prefill and decode) while the host is in the
program's ``serve.step`` span or one inside it (dispatch, finite gate,
argmax): the host side of each step that the device waits on."""
from bench import program_trace


def read(ctx):
    red = program_trace.for_ctx(ctx)
    steps = program_trace.count(red, "step") if red else 0
    if not steps:
        return None
    return 1e3 * red["idle_under"].get("serve.step", 0.0) / steps
