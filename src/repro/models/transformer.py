"""Dense decoder-only transformer (olmo-1b, qwen2.5-3b, phi4-mini, mistral-large).

Layers are *stacked* (leading layer axis) and applied with ``jax.lax.scan`` so
that 88-layer configs lower to a compact HLO — essential for the 40-combo
multi-pod dry-run.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.models import layers as L
from repro.models.base import ModelConfig


def remat_wrap(cfg: ModelConfig, body):
    return jax.checkpoint(body) if cfg.remat else body


def stack_init(fn, rng, n: int):
    """vmap an init function over n layer rngs -> stacked params."""
    return jax.vmap(fn)(jax.random.split(rng, n))


def init_block(rng, cfg: ModelConfig):
    ks = jax.random.split(rng, 2)
    return {
        "ln1": L.init_norm(cfg),
        "attn": L.init_attention(ks[0], cfg),
        "ln2": L.init_norm(cfg),
        "mlp": L.init_mlp(ks[1], cfg),
    }


def apply_block(bp, cfg: ModelConfig, h, *, positions=None, cache=None,
                cache_index=None):
    a, new_cache = L.apply_attention(
        bp["attn"], cfg, L.apply_norm(bp["ln1"], cfg, h),
        positions=positions, cache=cache, cache_index=cache_index)
    h = h + a
    h = h + L.apply_mlp(bp["mlp"], cfg, L.apply_norm(bp["ln2"], cfg, h))
    return h, new_cache


def init(rng, cfg: ModelConfig):
    ks = jax.random.split(rng, 3)
    return {
        "embed": L.init_embed(ks[0], cfg),
        "blocks": stack_init(lambda k: init_block(k, cfg), ks[1], cfg.n_layers),
        "final_norm": L.init_norm(cfg),
    }


def _scan_blocks(params, cfg: ModelConfig, h, *, positions=None, cache=None,
                 cache_index=None):
    """Run all blocks via scan. cache (if given) is stacked on layer axis."""

    def body(h, xs):
        bp, c = xs
        h, nc = apply_block(bp, cfg, h, positions=positions, cache=c,
                            cache_index=cache_index)
        return h, nc

    body = remat_wrap(cfg, body)
    h, new_cache = jax.lax.scan(body, h, (params["blocks"], cache))
    return h, new_cache


def forward(params, cfg: ModelConfig, tokens, *, positions=None, cache=None,
            cache_index=None):
    h = L.embed_tokens(params["embed"], tokens)
    h, new_cache = _scan_blocks(params, cfg, h, positions=positions,
                                cache=cache, cache_index=cache_index)
    h = L.apply_norm(params["final_norm"], cfg, h)
    return L.unembed(params["embed"], cfg, h), new_cache


def loss_fn(params, cfg: ModelConfig, batch):
    logits, _ = forward(params, cfg, batch["tokens"])
    return L.cross_entropy(logits[:, :-1], batch["labels"][:, 1:], cfg)


def init_cache(cfg: ModelConfig, batch: int, max_seq: int, dtype=None):
    c = L.init_kv_cache(cfg, batch, max_seq, dtype=dtype)
    return jax.tree.map(
        lambda x: jnp.broadcast_to(x[None], (cfg.n_layers,) + x.shape), c)


def prefill(params, cfg: ModelConfig, tokens, max_seq: Optional[int] = None):
    b, s = tokens.shape
    cache = init_cache(cfg, b, max_seq or s)
    logits, cache = forward(params, cfg, tokens, cache=cache, cache_index=0)
    return logits, cache


def decode_step(params, cfg: ModelConfig, cache, pos, tokens):
    """tokens: (b, 1); pos: scalar int32 index into the cache."""
    positions = pos + jnp.zeros((1,), jnp.int32)
    logits, cache = forward(params, cfg, tokens, positions=positions,
                            cache=cache, cache_index=pos)
    return logits, cache
