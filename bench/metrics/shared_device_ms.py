"""Device self ms per step under the decode step's ``shared`` scope: the
hybrid's shared transformer blocks (attention with its KV cache, MLP,
adapter and ``linear``), over the program's ``serve.step`` spans in the
traced window."""
from bench import program_trace


def read(ctx):
    return program_trace.scope_ms_per_step(program_trace.for_ctx(ctx),
                                           "shared")
