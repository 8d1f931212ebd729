"""Device self ms per step under the decode step's ``blocks`` scope: the
scan over the Mamba blocks (in_proj, conv, ssd, out_proj), over the
program's ``serve.step`` spans in the traced window."""
from bench import program_trace


def read(ctx):
    return program_trace.scope_ms_per_step(program_trace.for_ctx(ctx),
                                           "blocks")
