"""Zamba2-style hybrid: Mamba2 backbone + one *shared* attention+MLP block
applied every ``attn_every`` SSM layers [arXiv:2411.15242].

The shared block's weights are reused at every application site, but each
site keeps its own KV cache. Attention uses a sliding window
(cfg.sliding_window) so the ``long_500k`` decode shape stays sub-quadratic
with an O(window) ring-buffer cache.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro import obs
from repro.models import layers as L
from repro.models import mamba2 as M
from repro.models import transformer as T
from repro.models.base import ModelConfig


def n_groups(cfg: ModelConfig):
    return cfg.n_layers // cfg.attn_every, cfg.n_layers % cfg.attn_every


def init(rng, cfg: ModelConfig):
    ks = jax.random.split(rng, 4)
    return {
        "embed": L.init_embed(ks[0], cfg),
        "blocks": T.stack_init(lambda k: M.init_mamba_block(k, cfg), ks[1],
                               cfg.n_layers),
        "shared": T.init_block(ks[2], cfg),   # one attn+MLP block, reused
        "final_norm": L.init_norm(cfg),
    }


# -- ring-buffer windowed attention cache ----------------------------------


def init_attn_cache(cfg: ModelConfig, batch: int, max_seq: int):
    size = min(max_seq, cfg.sliding_window) if cfg.sliding_window else max_seq
    return L.init_kv_cache(cfg, batch, size)


def shared_attn_decode(bp, cfg: ModelConfig, h, attn_cache, pos):
    """One-token attention against a ring-buffer window cache."""
    x = L.apply_norm(bp["ln1"], cfg, h)
    o, new_cache = _ring_attention(bp["attn"], cfg, x, attn_cache, pos)
    h = h + o
    h = h + L.apply_mlp(bp["mlp"], cfg, L.apply_norm(bp["ln2"], cfg, h))
    return h, new_cache


@obs.scoped("attn")
def _ring_attention(p, cfg: ModelConfig, x, attn_cache, pos):
    b = x.shape[0]
    hd = cfg.resolved_head_dim
    nq, nkv = cfg.n_heads, cfg.n_kv_heads
    size = attn_cache["k"].shape[1]
    q = (x @ p["wq"]).reshape(b, 1, nq, hd)
    k = (x @ p["wk"]).reshape(b, 1, nkv, hd)
    v = (x @ p["wv"]).reshape(b, 1, nkv, hd)
    positions = pos + jnp.zeros((1,), jnp.int32)
    q = L.apply_rope(q, positions, cfg.rope_theta)
    k = L.apply_rope(k, positions, cfg.rope_theta)
    slot = jnp.mod(pos, size)
    ck = jax.lax.dynamic_update_slice(attn_cache["k"], k.astype(attn_cache["k"].dtype),
                                      (0, slot, 0, 0))
    cv = jax.lax.dynamic_update_slice(attn_cache["v"], v.astype(attn_cache["v"].dtype),
                                      (0, slot, 0, 0))
    kk = L._repeat_kv(ck, nq // nkv).astype(jnp.float32)
    vv = L._repeat_kv(cv, nq // nkv).astype(jnp.float32)
    with jax.named_scope("core"):
        s = jnp.einsum("bqhd,bkhd->bhqk", q.astype(jnp.float32) * hd ** -0.5,
                       kk)
        valid = jnp.arange(size) < jnp.minimum(pos + 1, size)
        s = jnp.where(valid[None, None, None], s, -1e30)
        pvals = jax.nn.softmax(s, axis=-1)
        o = jnp.einsum("bhqk,bkhd->bqhd", pvals, vv).astype(x.dtype)
    o = o.reshape(b, 1, nq * hd) @ p["wo"]
    return o, {"k": ck, "v": cv}


def init_cache(cfg: ModelConfig, batch: int, max_seq: int):
    ng, _ = n_groups(cfg)
    mc = M.init_block_cache(cfg, batch)
    mamba = jax.tree.map(
        lambda x: jnp.broadcast_to(x[None], (cfg.n_layers,) + x.shape), mc)
    ac = init_attn_cache(cfg, batch, max_seq)
    attn = jax.tree.map(
        lambda x: jnp.broadcast_to(x[None], (ng,) + x.shape), ac)
    return {"mamba": mamba, "attn": attn}


def forward_full(params, cfg: ModelConfig, tokens, *, mamba_cache=None,
                 collect_attn_kv: int = 0):
    """Train/prefill. If collect_attn_kv > 0, also build ring KV caches of
    that size for each shared-block application (for subsequent decode)."""
    ng, rem = n_groups(cfg)
    h = L.embed_tokens(params["embed"], tokens)
    per = cfg.attn_every
    blocks = params["blocks"]
    grouped = jax.tree.map(
        lambda x: x[:ng * per].reshape((ng, per) + x.shape[1:]), blocks)
    mcache = mamba_cache
    gm_cache = None
    if mcache is not None:
        gm_cache = jax.tree.map(
            lambda x: x[:ng * per].reshape((ng, per) + x.shape[1:]), mcache)

    def inner(h, xs):
        bp, c = xs
        h, nc = M.apply_mamba_block(bp, cfg, h, cache=c)
        return h, nc

    def group_body(h, xs):
        gbp, gc = xs
        h = T.seq_constraint(cfg, h)
        h, ncs = jax.lax.scan(inner, h, (gbp, gc))
        b, s, _ = h.shape
        x_in = L.apply_norm(params["shared"]["ln1"], cfg, h)
        a, _ = L.apply_attention(params["shared"]["attn"], cfg, x_in)
        h = h + a
        h = h + L.apply_mlp(params["shared"]["mlp"], cfg,
                            L.apply_norm(params["shared"]["ln2"], cfg, h))
        kv = None
        if collect_attn_kv:
            size = collect_attn_kv
            hd = cfg.resolved_head_dim
            k = (x_in @ params["shared"]["attn"]["wk"]).reshape(
                b, s, cfg.n_kv_heads, hd)
            v = (x_in @ params["shared"]["attn"]["wv"]).reshape(
                b, s, cfg.n_kv_heads, hd)
            k = L.apply_rope(k, jnp.arange(s), cfg.rope_theta)
            take = min(size, s)
            slots = jnp.mod(jnp.arange(s - take, s), size)
            ck = jnp.zeros((b, size, cfg.n_kv_heads, hd), cfg.dtype)
            ck = ck.at[:, slots].set(k[:, -take:].astype(cfg.dtype))
            cv = jnp.zeros((b, size, cfg.n_kv_heads, hd), cfg.dtype)
            cv = cv.at[:, slots].set(v[:, -take:].astype(cfg.dtype))
            kv = {"k": ck, "v": cv}
        return h, (ncs, kv)

    body = T.remat_wrap(cfg, group_body)
    h, (new_gm, attn_kv) = jax.lax.scan(body, h, (grouped, gm_cache))

    # remainder SSM layers (no shared block after them)
    if rem:
        tail = jax.tree.map(lambda x: x[ng * per:], blocks)
        tail_c = (jax.tree.map(lambda x: x[ng * per:], mcache)
                  if mcache is not None else None)
        h, new_tail = jax.lax.scan(inner, h, (tail, tail_c))
    else:
        new_tail = None

    h = L.apply_norm(params["final_norm"], cfg, h)
    logits = L.unembed(params["embed"], cfg, h)

    new_mcache = None
    if mcache is not None:
        new_mcache = jax.tree.map(
            lambda g: g.reshape((ng * per,) + g.shape[2:]), new_gm)
        if rem:
            new_mcache = jax.tree.map(
                lambda a, b_: jnp.concatenate([a, b_], axis=0),
                new_mcache, new_tail)
    return logits, new_mcache, attn_kv


def loss_fn(params, cfg: ModelConfig, batch):
    logits, _, _ = forward_full(params, cfg, batch["tokens"])
    return L.cross_entropy(logits[:, :-1], batch["labels"][:, 1:], cfg)


def prefill(params, cfg: ModelConfig, tokens, max_seq: Optional[int] = None):
    b, s = tokens.shape
    max_seq = max_seq or s
    size = min(max_seq, cfg.sliding_window) if cfg.sliding_window else max_seq
    mcache = M.init_cache(cfg, b)
    logits, new_m, attn_kv = forward_full(params, cfg, tokens,
                                          mamba_cache=mcache,
                                          collect_attn_kv=size)
    return logits, {"mamba": new_m, "attn": attn_kv}


def decode_step(params, cfg: ModelConfig, cache, pos, tokens):
    ng, rem = n_groups(cfg)
    per = cfg.attn_every
    h = L.embed_tokens(params["embed"], tokens)
    blocks = params["blocks"]
    grouped = jax.tree.map(
        lambda x: x[:ng * per].reshape((ng, per) + x.shape[1:]), blocks)
    gm_cache = jax.tree.map(
        lambda x: x[:ng * per].reshape((ng, per) + x.shape[1:]),
        cache["mamba"])

    def inner(h, xs):
        bp, c = xs
        h, nc = M.apply_mamba_decode(bp, cfg, h, c)
        return h, nc

    def group_body(h, xs):
        gbp, gc, ac = xs
        h, ncs = jax.lax.scan(inner, h, (gbp, gc))
        h, nac = shared_attn_decode(params["shared"], cfg, h, ac, pos)
        return h, (ncs, nac)

    h, (new_gm, new_attn) = jax.lax.scan(group_body, h,
                                         (grouped, gm_cache, cache["attn"]))
    new_m = jax.tree.map(lambda g: g.reshape((ng * per,) + g.shape[2:]), new_gm)
    if rem:
        tail = jax.tree.map(lambda x: x[ng * per:], blocks)
        tail_c = jax.tree.map(lambda x: x[ng * per:], cache["mamba"])
        h, new_tail = jax.lax.scan(inner, h, (tail, tail_c))
        new_m = jax.tree.map(lambda a, b_: jnp.concatenate([a, b_], axis=0),
                             new_m, new_tail)
    h = L.apply_norm(params["final_norm"], cfg, h)
    logits = L.unembed(params["embed"], cfg, h)
    return logits, {"mamba": new_m, "attn": new_attn}
