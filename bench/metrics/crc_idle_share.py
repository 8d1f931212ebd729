"""Share of the traced window in which the device idles while the host is
in a health-monitor table CRC (``serve.monitor.crc_layer`` or
``serve.monitor.crc_head``, or a span inside one), in %."""
from bench import program_trace

CRC = ("serve.monitor.crc_layer", "serve.monitor.crc_head")


def read(ctx):
    red = program_trace.for_ctx(ctx)
    if not red or not program_trace.count(red, "monitor"):
        return None
    # a CRC span never holds the other, so their idle times add
    idle = sum(red["idle_under"].get(n, 0.0) for n in CRC)
    return 100.0 * idle / red["window_s"]
