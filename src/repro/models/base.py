"""Model configuration shared across all architecture families.

One dataclass covers every assigned family (dense / moe / ssm / hybrid /
vlm / audio enc-dec); family-specific fields default to "off".
"""
from __future__ import annotations

import dataclasses
from typing import Any

import jax.numpy as jnp


def round_up(x: int, m: int) -> int:
    return ((x + m - 1) // m) * m


@dataclasses.dataclass(frozen=True)
class ModelConfig:
    # identity
    arch_id: str = "unnamed"
    family: str = "dense"  # dense | moe | ssm | hybrid | vlm | audio
    source: str = ""       # citation for the assigned config

    # core transformer dims
    n_layers: int = 2
    d_model: int = 256
    n_heads: int = 4
    n_kv_heads: int = 4
    d_ff: int = 1024
    vocab_size: int = 1024

    # attention details
    head_dim: int = 0           # 0 -> d_model // n_heads
    qkv_bias: bool = False
    rope_theta: float = 10_000.0
    sliding_window: int = 0     # 0 -> full causal attention
    # norm: "rmsnorm" | "layernorm" | "nonparametric_ln" (OLMo)
    norm: str = "rmsnorm"
    # mlp: "swiglu" | "gelu"
    mlp: str = "swiglu"
    tie_embeddings: bool = False

    # MoE
    n_experts: int = 0          # 0 -> dense FFN
    n_shared_experts: int = 0   # Qwen2-MoE style always-on experts
    top_k: int = 0
    moe_dense_residual: bool = False  # Arctic: dense FFN residual in parallel
    router_aux_weight: float = 0.01
    moe_capacity_factor: float = 1.25  # expert capacity = cf * k * T / E

    # SSM (Mamba2 / SSD)
    ssm_state: int = 0          # 0 -> no ssm
    ssm_expand: int = 2
    ssm_headdim: int = 64
    ssm_chunk: int = 256
    ssm_conv_width: int = 4
    # hybrid (Zamba2): apply one *shared* attention block every k ssm layers
    attn_every: int = 0         # 0 -> no interleaved attention

    # VLM (Llama-3.2-Vision style): cross-attention image layers
    cross_attn_every: int = 0   # every k-th layer is a cross-attn layer
    n_image_tokens: int = 0
    d_vision: int = 0           # vision embedding width from the (stubbed) ViT

    # audio enc-dec (Seamless style)
    n_encoder_layers: int = 0   # >0 -> encoder-decoder model
    n_audio_frames: int = 0
    d_audio: int = 0            # frame embedding width from the (stubbed) codec

    # numerics / performance knobs
    dtype: Any = jnp.float32
    remat: bool = False

    # -- derived -------------------------------------------------------------
    @property
    def resolved_head_dim(self) -> int:
        return self.head_dim or (self.d_model // self.n_heads)

    @property
    def vocab_padded(self) -> int:
        """Vocab padded to a multiple of 128 so TP=16 shards evenly and the
        unembed matmul stays MXU-aligned. Loss masks the padding columns."""
        return round_up(self.vocab_size, 128)

    @property
    def d_inner(self) -> int:
        """Mamba2 inner width."""
        return self.ssm_expand * self.d_model

    @property
    def ssm_nheads(self) -> int:
        return self.d_inner // self.ssm_headdim

    @property
    def is_encdec(self) -> bool:
        return self.n_encoder_layers > 0

    def replace(self, **kw) -> "ModelConfig":
        return dataclasses.replace(self, **kw)

    # parameters in the initialised tree, every expert counted
    def param_count(self) -> int:
        from repro.models import registry  # local import to avoid cycles
        return registry.param_count(self)

    def active_param_count(self) -> int:
        from repro.models import registry
        return registry.param_count(self, active_only=True)


@dataclasses.dataclass(frozen=True)
class InputShape:
    """One of the assigned workload shapes."""
    name: str
    seq_len: int
    global_batch: int
    kind: str  # "train" | "prefill" | "decode"


INPUT_SHAPES = {
    "train_4k": InputShape("train_4k", 4_096, 256, "train"),
    "prefill_32k": InputShape("prefill_32k", 32_768, 32, "prefill"),
    "decode_32k": InputShape("decode_32k", 32_768, 128, "decode"),
    "long_500k": InputShape("long_500k", 524_288, 1, "decode"),
}
