"""Device self ms per step under the decode step's ``head`` scope: the
logits head's kernel with the ops around it (the pool's transpose and pad
included), over the program's ``serve.step`` spans in the traced window."""
from bench import program_trace


def read(ctx):
    return program_trace.scope_ms_per_step(program_trace.for_ctx(ctx),
                                           "head")
