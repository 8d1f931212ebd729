"""Config dataclasses for the model zoo.

Every assigned architecture is a :class:`ModelConfig`; ``configs/<id>.py``
exports ``config()`` (the exact published shape) and ``smoke_config()`` (a
reduced same-family variant for CPU tests).

Sharding-driven padding: ``pad_heads_to`` / ``pad_experts_to`` round head and
expert counts up so they divide the production 16-way model axis (Megatron's
divisible-size trick).  Padding is part of the *config* (mesh-independent) so
checkpoints stay elastic across meshes; smoke configs use no padding and the
dry-run report carries both nominal and padded parameter counts.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Optional, Tuple

import jax.numpy as jnp

__all__ = ["ModelConfig", "MoEConfig", "SSMConfig", "PCILTConfig", "ShapeConfig",
           "SHAPES"]


@dataclasses.dataclass(frozen=True)
class MoEConfig:
    n_experts: int
    top_k: int
    d_ff_expert: int
    interleave: int = 1          # MoE every `interleave` layers (2 = alternate)
    shared_expert: bool = False  # always-on shared expert (llama4)
    capacity_factor: float = 1.25
    pad_experts_to: int = 0      # 0 = no padding

    @property
    def padded_experts(self) -> int:
        return max(self.n_experts, self.pad_experts_to)


@dataclasses.dataclass(frozen=True)
class SSMConfig:
    d_state: int = 128
    head_dim: int = 64
    n_groups: int = 1
    conv_kernel: int = 4
    expand: int = 2
    chunk: int = 256
    dt_rank: int = 0  # unused in SSD; kept for provenance
    #: SSD heads this chip holds (0 = all): a tensor-parallel share takes
    #: whole heads, with the B/C state projections of the groups they sit in
    heads_held: int = 0


@dataclasses.dataclass(frozen=True)
class PCILTConfig:
    """Paper-technique integration for quantized serving (DESIGN.md §6)."""

    act_bits: int = 4
    group: int = 2
    weight_bits: int = 4
    apply_to_conv: bool = True   # frontends (mamba/whisper/llava)
    apply_to_gemv: bool = True   # decode projections


@dataclasses.dataclass(frozen=True)
class ModelConfig:
    name: str
    family: str                  # dense | moe | ssm | hybrid | audio | vlm
    n_layers: int
    d_model: int
    n_heads: int
    n_kv_heads: int
    d_ff: int
    vocab: int
    head_dim: int = 0            # 0 -> d_model // n_heads
    # attention variants
    qkv_bias: bool = False
    qk_norm: bool = False
    window: int = 0              # sliding-window size (0 = full attention)
    rope_theta: float = 10000.0
    pos_embed: str = "rope"      # rope | sinusoidal | none
    # families
    moe: Optional[MoEConfig] = None
    ssm: Optional[SSMConfig] = None
    # zamba2: the layers that run a shared transformer block before their
    # Mamba mixer, the shared blocks they take in turn, and the rank of the
    # LoRA adapter each application adds to the shared MLP
    hybrid_layer_ids: Tuple[int, ...] = ()
    n_shared_attn_blocks: int = 2
    adapter_rank: int = 0
    encoder_layers: int = 0      # whisper enc-dec
    encoder_len: int = 1500
    n_img_tokens: int = 0        # llava stub frontend
    # sharding-driven padding (see module docstring)
    pad_heads_to: int = 0
    pad_kv_heads_to: int = 0
    # numerics / structure
    tie_embeddings: bool = False
    norm_eps: float = 1e-5
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.float32
    remat_policy: str = "dots"   # nothing | dots | full
    scan_layers: bool = True
    loss_chunk: int = 2048       # vocab-loss token chunking (0 = unchunked)
    grad_accum: int = 1          # microbatches per step (memory / collective
                                 # trade: activations ÷ n, weight gathers × n)
    pcilt: Optional[PCILTConfig] = None

    @property
    def resolved_head_dim(self) -> int:
        return self.head_dim or self.d_model // self.n_heads

    @property
    def padded_vocab(self) -> int:
        """Vocab rounded up to a multiple of 16 so the logits/embedding shard
        over the 16-way model axis (Megatron's divisible-vocab trick; padded
        ids are never produced by data or sampling)."""
        return self.vocab + (-self.vocab) % 16

    @property
    def padded_heads(self) -> int:
        return max(self.n_heads, self.pad_heads_to)

    @property
    def padded_kv_heads(self) -> int:
        return max(self.n_kv_heads, self.pad_kv_heads_to)

    @property
    def attention_free(self) -> bool:
        return self.family == "ssm"

    @property
    def sub_quadratic(self) -> bool:
        """Eligible for the long_500k shape (DESIGN.md §7)."""
        return self.family in ("ssm", "hybrid") or self.window > 0


@dataclasses.dataclass(frozen=True)
class ShapeConfig:
    name: str
    seq_len: int
    global_batch: int
    kind: str  # train | prefill | decode


SHAPES = {
    "train_4k": ShapeConfig("train_4k", 4096, 256, "train"),
    "prefill_32k": ShapeConfig("prefill_32k", 32768, 32, "prefill"),
    "decode_32k": ShapeConfig("decode_32k", 32768, 128, "decode"),
    "long_500k": ShapeConfig("long_500k", 524288, 1, "decode"),
}
