"""Mean host ms of the health monitor's pass per decode tick
(``HealthMonitor.on_tick``), head-CRC ticks included."""
from bench import window


def read(ctx):
    d = window.spans(ctx.spans, "monitor", ctx.w0, ctx.w1)
    return 1e3 * sum(d) / len(d) if d else None
