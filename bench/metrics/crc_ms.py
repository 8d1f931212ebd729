"""Host ms per decode tick in the health monitor's table CRCs: the
program's ``serve.monitor.crc_layer`` and ``serve.monitor.crc_head`` spans
over its ``serve.monitor`` spans (one a decode tick), in the traced window."""
from bench import program_trace


def read(ctx):
    red = program_trace.for_ctx(ctx)
    ticks = program_trace.count(red, "monitor") if red else 0
    if not ticks:
        return None
    crc = program_trace.span_seconds(red, "monitor.crc_layer",
                                     "monitor.crc_head")
    return 1e3 * crc / ticks
