"""Whole step's share of the chips' bf16 peak, in %: the dense model's
matmul FLOPs per output token (the family's ``dense_flops_per_token``)
times the traced run's tokens/s."""
from bench import roofline, window


def read(ctx):
    rate = window.tok_s(ctx.token_times, ctx.w0, ctx.w1)
    if not rate:
        return None
    peak = roofline.peaks(ctx.device_kind)["bf16_flops"] * ctx.chips
    return 100.0 * ctx.family.dense_flops_per_token(ctx.conf) * rate / peak
