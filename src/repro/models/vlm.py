"""Llama-3.2-Vision-style VLM decoder [hf:meta-llama/Llama-3.2-11B-Vision].

Language backbone only (per the brief): the ViT/SigLIP vision encoder is a
STUB — ``input_specs()`` supplies precomputed patch embeddings
(b, n_image_tokens, d_vision). The backbone is a dense GQA decoder where
every ``cross_attn_every``-th layer is a gated cross-attention layer over the
projected image tokens. Layers are organized as scanned groups of
(cross_attn_every - 1) self layers + 1 cross layer.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.models import layers as L
from repro.models import transformer as T
from repro.models.base import ModelConfig


def group_shape(cfg: ModelConfig):
    per = cfg.cross_attn_every
    assert cfg.n_layers % per == 0, "n_layers must divide into cross groups"
    return cfg.n_layers // per, per - 1  # (n_groups, self_layers_per_group)


def init_cross_block(rng, cfg: ModelConfig):
    ks = jax.random.split(rng, 2)
    return {
        "ln1": L.init_norm(cfg),
        "attn": L.init_attention(ks[0], cfg),
        "ln2": L.init_norm(cfg),
        "mlp": L.init_mlp(ks[1], cfg),
        "gate_attn": jnp.zeros((), cfg.dtype),   # tanh-gated residuals
        "gate_mlp": jnp.zeros((), cfg.dtype),
    }


def apply_cross_block(bp, cfg: ModelConfig, h, image_kv):
    """image_kv: {"k": (b, n_img, kv, hd), "v": ...} precomputed."""
    o = L.cross_attention(bp["attn"], cfg, L.apply_norm(bp["ln1"], cfg, h),
                          image_kv)
    h = h + jnp.tanh(bp["gate_attn"]) * o
    m = L.apply_mlp(bp["mlp"], cfg, L.apply_norm(bp["ln2"], cfg, h))
    return h + jnp.tanh(bp["gate_mlp"]) * m


def image_kv_from_embeds(params, cfg: ModelConfig, image_embeds):
    """Project stubbed vision embeddings and precompute per-group cross K/V.
    image_embeds: (b, n_img, d_vision) -> stacked {"k","v"}: (G, b, n_img, kv, hd)."""
    b, n_img, _ = image_embeds.shape
    hd = cfg.resolved_head_dim
    x = image_embeds @ params["vision_proj"]   # (b, n_img, d_model)

    def one(cbp):
        k = (x @ cbp["attn"]["wk"]).reshape(b, n_img, cfg.n_kv_heads, hd)
        v = (x @ cbp["attn"]["wv"]).reshape(b, n_img, cfg.n_kv_heads, hd)
        return {"k": k, "v": v}

    return jax.vmap(one)(params["cross"])


def init(rng, cfg: ModelConfig):
    ng, per_self = group_shape(cfg)
    ks = jax.random.split(rng, 5)
    return {
        "embed": L.init_embed(ks[0], cfg),
        "vision_proj": L.dense_init(ks[1], cfg.d_vision, cfg.d_model, cfg.dtype),
        "blocks": T.stack_init(lambda k: T.init_block(k, cfg), ks[2],
                               ng * per_self),
        "cross": T.stack_init(lambda k: init_cross_block(k, cfg), ks[3], ng),
        "final_norm": L.init_norm(cfg),
    }


def forward(params, cfg: ModelConfig, tokens, image_kv, *, positions=None,
            self_cache=None, cache_index=None):
    ng, per_self = group_shape(cfg)
    h = L.embed_tokens(params["embed"], tokens)
    grouped = jax.tree.map(
        lambda x: x.reshape((ng, per_self) + x.shape[1:]), params["blocks"])
    gcache = None
    if self_cache is not None:
        gcache = jax.tree.map(
            lambda x: x.reshape((ng, per_self) + x.shape[1:]), self_cache)

    def inner(h, xs):
        bp, c = xs
        h, nc = T.apply_block(bp, cfg, h, positions=positions, cache=c,
                              cache_index=cache_index)
        return h, nc

    def group_body(h, xs):
        gbp, cbp, gc, ikv = xs
        h = T.seq_constraint(cfg, h) if self_cache is None else h
        h, ncs = jax.lax.scan(inner, h, (gbp, gc))
        h = apply_cross_block(cbp, cfg, h, ikv)
        return h, ncs

    body = T.remat_wrap(cfg, group_body)
    h, new_g = jax.lax.scan(body, h, (grouped, params["cross"], gcache,
                                      image_kv))
    h = L.apply_norm(params["final_norm"], cfg, h)
    logits = L.unembed(params["embed"], cfg, h)
    new_cache = None
    if self_cache is not None:
        new_cache = jax.tree.map(
            lambda x: x.reshape((ng * per_self,) + x.shape[2:]), new_g)
    return logits, new_cache


def loss_fn(params, cfg: ModelConfig, batch):
    ikv = image_kv_from_embeds(params, cfg, batch["image_embeds"])
    logits, _ = forward(params, cfg, batch["tokens"], ikv)
    return L.cross_entropy(logits[:, :-1], batch["labels"][:, 1:], cfg)


def init_self_cache(cfg: ModelConfig, batch: int, max_seq: int):
    ng, per_self = group_shape(cfg)
    c = L.init_kv_cache(cfg, batch, max_seq)
    return jax.tree.map(
        lambda x: jnp.broadcast_to(x[None], (ng * per_self,) + x.shape), c)


def prefill(params, cfg: ModelConfig, tokens, image_embeds,
            max_seq: Optional[int] = None):
    b, s = tokens.shape
    ikv = image_kv_from_embeds(params, cfg, image_embeds)
    self_cache = init_self_cache(cfg, b, max_seq or s)
    logits, self_cache = forward(params, cfg, tokens, ikv,
                                 self_cache=self_cache, cache_index=0)
    return logits, {"self": self_cache, "image_kv": ikv}


def decode_step(params, cfg: ModelConfig, cache, pos, tokens):
    positions = pos + jnp.zeros((1,), jnp.int32)
    logits, new_self = forward(params, cfg, tokens, cache["image_kv"],
                               positions=positions, self_cache=cache["self"],
                               cache_index=pos)
    return logits, {"self": new_self, "image_kv": cache["image_kv"]}
