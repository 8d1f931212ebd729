"""zamba2-7b [hybrid] — Zamba2-7B-Instruct (arXiv:2411.15242;
huggingface.co/Zyphra/Zamba2-7B-Instruct config.json): 81 Mamba2 layers
d3584 (112 heads of 64, d_state 64, 2 groups, conv 4, expand 2); the 13
layers of ``hybrid_layer_ids`` first run one of 2 shared transformer blocks
in turn on concat(hidden, embedding), 7168 wide: 32 heads of 224 with
RoPE, a gelu MLP 14336 wide with a rank-128 LoRA adapter per application,
then a per-application 3584x3584 ``linear`` whose output joins the Mamba
mixer's input.  Vocab 32000, tied head (``models/hybrid.py``).
"""

from .base import ModelConfig, SSMConfig


def config():
    return ModelConfig(
        name="zamba2-7b", family="hybrid",
        n_layers=81, d_model=3584, n_heads=32, n_kv_heads=32,
        d_ff=14336, vocab=32000, head_dim=224,
        ssm=SSMConfig(d_state=64, head_dim=64, n_groups=2, conv_kernel=4,
                      expand=2, chunk=256),
        hybrid_layer_ids=(6, 11, 17, 23, 29, 35, 41, 47, 53, 59, 65, 71, 77),
        n_shared_attn_blocks=2, adapter_rank=128, tie_embeddings=True,
        remat_policy="full", loss_chunk=2048,
    )


def smoke_config():
    """The published structure at toy widths: 2 groups, 2 shared blocks
    applied at two hybrid layers of a 7-layer stack, adapters, ``linear``."""
    return ModelConfig(
        name="zamba2-smoke", family="hybrid",
        n_layers=7, d_model=64, n_heads=4, n_kv_heads=4,
        d_ff=128, vocab=256, head_dim=32,
        ssm=SSMConfig(d_state=16, head_dim=16, n_groups=2, conv_kernel=4,
                      expand=2, chunk=16),
        hybrid_layer_ids=(2, 5), n_shared_attn_blocks=2, adapter_rank=8,
        tie_embeddings=True, remat_policy="none", loss_chunk=0,
    )
