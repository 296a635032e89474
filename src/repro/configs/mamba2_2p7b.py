"""mamba2-2.7b — SSD (state-space duality) [arXiv:2405.21060].

64L d_model=2560, attention-free, d_ff=0, ssm_state=128. The vocabulary is
the published checkpoint's (huggingface.co/state-spaces/mamba2-2.7b,
config.json: vocab_size 50277, padded there to a multiple of 16); the
program pads it to a multiple of 128 and the loss masks the padding.
"""
import jax.numpy as jnp

from repro.models.base import ModelConfig

CONFIG = ModelConfig(
    arch_id="mamba2-2.7b",
    family="ssm",
    source="arXiv:2405.21060",
    n_layers=64,
    d_model=2560,
    n_heads=0,
    n_kv_heads=0,
    d_ff=0,
    vocab_size=50_277,
    ssm_state=128,
    ssm_expand=2,
    ssm_headdim=64,
    ssm_chunk=256,
    tie_embeddings=True,
    norm="rmsnorm",
    dtype=jnp.bfloat16,
)
