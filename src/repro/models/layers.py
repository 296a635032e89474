"""Shared neural-net primitives for the model zoo.

Pure-functional: parameters are nested dicts of jnp arrays, every layer is
``init_*`` (build params) + ``apply`` function. Attention is implemented with
a blockwise online-softmax formulation so that 32k-token prefill lowers with
O(block x seq) live memory instead of O(seq^2) — the jnp analogue of the
Pallas flash-attention kernels in ``repro.kernels.flash_attention``, which
causal self-attention takes where they compile
(``repro.kernels.flash_attention_applies``).
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro import kernels, obs
from repro.kernels import ops as kops
from repro.models.base import ModelConfig

# ---------------------------------------------------------------------------
# init helpers
# ---------------------------------------------------------------------------


def dense_init(rng, d_in: int, d_out: int, dtype, scale: Optional[float] = None):
    scale = scale if scale is not None else d_in ** -0.5
    return (jax.random.normal(rng, (d_in, d_out)) * scale).astype(dtype)


def embed_init(rng, vocab: int, d_model: int, dtype):
    return (jax.random.normal(rng, (vocab, d_model)) * 0.02).astype(dtype)


# ---------------------------------------------------------------------------
# norms
# ---------------------------------------------------------------------------


def init_norm(cfg: ModelConfig, dtype=None):
    dtype = dtype or cfg.dtype
    if cfg.norm == "rmsnorm":
        return {"scale": jnp.ones((cfg.d_model,), dtype)}
    if cfg.norm == "layernorm":
        return {"scale": jnp.ones((cfg.d_model,), dtype),
                "bias": jnp.zeros((cfg.d_model,), dtype)}
    if cfg.norm == "nonparametric_ln":  # OLMo: LN without affine params
        return {}
    raise ValueError(f"unknown norm {cfg.norm!r}")


@obs.scoped("norm")
def apply_norm(params, cfg: ModelConfig, x, eps: float = 1e-5):
    xf = x.astype(jnp.float32)
    if cfg.norm == "rmsnorm":
        y = xf * jax.lax.rsqrt(jnp.mean(xf * xf, axis=-1, keepdims=True) + eps)
        return (y * params["scale"].astype(jnp.float32)).astype(x.dtype)
    mu = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(xf - mu), axis=-1, keepdims=True)
    y = (xf - mu) * jax.lax.rsqrt(var + eps)
    if cfg.norm == "layernorm":
        y = y * params["scale"].astype(jnp.float32) + params["bias"].astype(jnp.float32)
    return y.astype(x.dtype)


@obs.scoped("norm")
def rmsnorm_raw(x, scale, eps: float = 1e-5):
    xf = x.astype(jnp.float32)
    y = xf * jax.lax.rsqrt(jnp.mean(xf * xf, axis=-1, keepdims=True) + eps)
    return (y * scale.astype(jnp.float32)).astype(x.dtype)


# ---------------------------------------------------------------------------
# rotary position embedding
# ---------------------------------------------------------------------------


def rope_freqs(head_dim: int, theta: float):
    return 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim))


def apply_rope(x, positions, theta: float):
    """x: (b, s, h, d); positions: (b, s) or (s,) int32."""
    d = x.shape[-1]
    freqs = rope_freqs(d, theta)                      # (d/2,)
    angles = positions[..., None].astype(jnp.float32) * freqs  # (..., s, d/2)
    if angles.ndim == 2:  # (s, d/2) -> broadcast over batch
        angles = angles[None]
    cos = jnp.cos(angles)[:, :, None, :]
    sin = jnp.sin(angles)[:, :, None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# attention
# ---------------------------------------------------------------------------


def init_attention(rng, cfg: ModelConfig, d_model: Optional[int] = None,
                   n_heads: Optional[int] = None, n_kv: Optional[int] = None,
                   cross: bool = False):
    d_model = d_model or cfg.d_model
    n_heads = n_heads or cfg.n_heads
    n_kv = n_kv or cfg.n_kv_heads
    hd = cfg.resolved_head_dim
    ks = jax.random.split(rng, 4)
    p = {
        "wq": dense_init(ks[0], d_model, n_heads * hd, cfg.dtype),
        "wk": dense_init(ks[1], d_model, n_kv * hd, cfg.dtype),
        "wv": dense_init(ks[2], d_model, n_kv * hd, cfg.dtype),
        "wo": dense_init(ks[3], n_heads * hd, d_model, cfg.dtype),
    }
    if cfg.qkv_bias:
        p["bq"] = jnp.zeros((n_heads * hd,), cfg.dtype)
        p["bk"] = jnp.zeros((n_kv * hd,), cfg.dtype)
        p["bv"] = jnp.zeros((n_kv * hd,), cfg.dtype)
    return p


def _repeat_kv(x, n_rep: int):
    """(b, s, kv, d) -> (b, s, kv*n_rep, d) by head-group broadcast."""
    if n_rep == 1:
        return x
    b, s, kv, d = x.shape
    return jnp.broadcast_to(x[:, :, :, None, :], (b, s, kv, n_rep, d)).reshape(
        b, s, kv * n_rep, d)


@obs.scoped("core")
def blockwise_attention(q, k, v, *, causal: bool, q_offset=0,
                        sliding_window: int = 0, q_block: int = 512):
    """Online-softmax attention, scanned over query blocks.

    q: (b, sq, h, d); k, v: (b, skv, h, d). ``q_offset`` is the absolute
    position of q[0] relative to k[0] (decode: q_offset = cache length).
    Peak live memory is O(b*h*q_block*skv) rather than O(sq*skv).
    """
    obs.count("attn.blockwise")
    b, sq, h, d = q.shape
    skv = k.shape[1]
    scale = d ** -0.5
    qf = q.astype(jnp.float32) * scale
    kf = k.astype(jnp.float32)
    vf = v.astype(jnp.float32)
    kv_pos = jnp.arange(skv)

    q_block = min(q_block, sq)
    pad = (-sq) % q_block
    if pad:
        qf = jnp.pad(qf, ((0, 0), (0, pad), (0, 0), (0, 0)))
    n_blocks = qf.shape[1] // q_block
    qf = qf.reshape(b, n_blocks, q_block, h, d).transpose(1, 0, 2, 3, 4)

    def one_block(carry, args):
        qb, blk_idx = args
        q_pos = q_offset + blk_idx * q_block + jnp.arange(q_block)
        s = jnp.einsum("bqhd,bkhd->bhqk", qb, kf)
        mask = jnp.ones((q_block, skv), bool)
        if causal:
            mask &= q_pos[:, None] >= kv_pos[None, :]
        if sliding_window:
            mask &= q_pos[:, None] - kv_pos[None, :] < sliding_window
        s = jnp.where(mask[None, None], s, -1e30)
        m = jnp.max(s, axis=-1, keepdims=True)
        p = jnp.exp(s - m)
        denom = jnp.sum(p, axis=-1, keepdims=True)
        o = jnp.einsum("bhqk,bkhd->bqhd", p / jnp.maximum(denom, 1e-30), vf)
        return carry, o

    _, outs = jax.lax.scan(one_block, None,
                           (qf, jnp.arange(n_blocks)))
    out = outs.transpose(1, 0, 2, 3, 4).reshape(b, n_blocks * q_block, h, d)
    return out[:, :sq].astype(q.dtype)


@obs.scoped("core")
def flash_attention(q, k, v, *, sliding_window: int = 0):
    """Causal self-attention through the Pallas flash kernels, forward and
    backward (``repro.kernels.ops.flash_attention``); same layout and
    result as ``blockwise_attention(q, k, v, causal=True)``."""
    obs.count("attn.flash")
    out = kops.flash_attention(
        q.transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3),
        v.transpose(0, 2, 1, 3), causal=True, window=sliding_window)
    return out.transpose(0, 2, 1, 3)


@obs.scoped("attn")
def apply_attention(params, cfg: ModelConfig, x, *, positions=None,
                    causal: bool = True, cache: Optional[dict] = None,
                    cache_index=None, kv_input=None, use_rope: bool = True,
                    sliding_window: Optional[int] = None):
    """GQA attention with optional KV cache and cross-attention.

    cache: {"k": (b, max_s, kv, d), "v": ...} updated functionally; returns
    (out, new_cache). ``kv_input`` switches to cross-attention (no cache
    append, kv computed from ``kv_input``).
    """
    b, s, _ = x.shape
    hd = cfg.resolved_head_dim
    nq = params["wq"].shape[1] // hd
    nkv = params["wk"].shape[1] // hd
    window = cfg.sliding_window if sliding_window is None else sliding_window

    q = x @ params["wq"]
    kv_src = kv_input if kv_input is not None else x
    k = kv_src @ params["wk"]
    v = kv_src @ params["wv"]
    if "bq" in params:
        q = q + params["bq"]
        k = k + params["bk"]
        v = v + params["bv"]
    q = q.reshape(b, s, nq, hd)
    k = k.reshape(b, kv_src.shape[1], nkv, hd)
    v = v.reshape(b, kv_src.shape[1], nkv, hd)

    if positions is None:
        positions = jnp.arange(s)
    if use_rope and kv_input is None:
        q = apply_rope(q, positions, cfg.rope_theta)
        k = apply_rope(k, positions, cfg.rope_theta)

    q_offset = 0
    new_cache = cache
    if cache is not None and kv_input is None:
        # functional cache append at cache_index (decode: s == 1)
        idx = cache_index if cache_index is not None else 0
        ck = jax.lax.dynamic_update_slice(cache["k"], k.astype(cache["k"].dtype),
                                          (0, idx, 0, 0))
        cv = jax.lax.dynamic_update_slice(cache["v"], v.astype(cache["v"].dtype),
                                          (0, idx, 0, 0))
        new_cache = {"k": ck, "v": cv}
        k, v = ck, cv
        q_offset = idx

    n_rep = nq // nkv
    k = _repeat_kv(k, n_rep)
    v = _repeat_kv(v, n_rep)
    if kernels.flash_attention_applies(
            s, causal=causal, self_attention=cache is None and kv_input is None):
        out = flash_attention(q, k, v, sliding_window=window)
    else:
        out = blockwise_attention(q, k, v, causal=causal, q_offset=q_offset,
                                  sliding_window=window)
    out = out.reshape(b, s, nq * hd) @ params["wo"]
    return out, new_cache


@obs.scoped("attn")
def cross_attention(params, cfg: ModelConfig, x, kv):
    """x: (b, s, d) attending, unmasked, over precomputed K/V
    {"k", "v"}: (b, n, kv, hd) (image tokens, encoder frames)."""
    b, s, _ = x.shape
    hd = cfg.resolved_head_dim
    n_rep = cfg.n_heads // cfg.n_kv_heads
    q = (x @ params["wq"]).reshape(b, s, cfg.n_heads, hd)
    o = blockwise_attention(q, _repeat_kv(kv["k"], n_rep),
                            _repeat_kv(kv["v"], n_rep), causal=False)
    return o.reshape(b, s, cfg.n_heads * hd) @ params["wo"]


def init_kv_cache(cfg: ModelConfig, batch: int, max_seq: int, n_kv=None,
                  dtype=None):
    nkv = n_kv or cfg.n_kv_heads
    dtype = dtype or cfg.dtype
    hd = cfg.resolved_head_dim
    return {"k": jnp.zeros((batch, max_seq, nkv, hd), dtype),
            "v": jnp.zeros((batch, max_seq, nkv, hd), dtype)}


# ---------------------------------------------------------------------------
# MLP
# ---------------------------------------------------------------------------


def init_mlp(rng, cfg: ModelConfig, d_ff: Optional[int] = None,
             d_model: Optional[int] = None):
    d_ff = d_ff or cfg.d_ff
    d_model = d_model or cfg.d_model
    ks = jax.random.split(rng, 3)
    if cfg.mlp == "swiglu":
        return {"wi": dense_init(ks[0], d_model, d_ff, cfg.dtype),
                "wg": dense_init(ks[1], d_model, d_ff, cfg.dtype),
                "wo": dense_init(ks[2], d_ff, d_model, cfg.dtype)}
    return {"wi": dense_init(ks[0], d_model, d_ff, cfg.dtype),
            "wo": dense_init(ks[2], d_ff, d_model, cfg.dtype)}


@obs.scoped("mlp")
def apply_mlp(params, cfg: ModelConfig, x):
    if "wg" in params:
        return (jax.nn.silu(x @ params["wi"]) * (x @ params["wg"])) @ params["wo"]
    return jax.nn.gelu(x @ params["wi"]) @ params["wo"]


# ---------------------------------------------------------------------------
# embedding / unembedding / loss
# ---------------------------------------------------------------------------


def init_embed(rng, cfg: ModelConfig):
    ks = jax.random.split(rng, 2)
    p = {"tok": embed_init(ks[0], cfg.vocab_padded, cfg.d_model, cfg.dtype)}
    if not cfg.tie_embeddings:
        p["unembed"] = dense_init(ks[1], cfg.d_model, cfg.vocab_padded, cfg.dtype)
    return p


@obs.scoped("embed")
def embed_tokens(params, x):
    return jnp.take(params["tok"], x, axis=0)


@obs.scoped("unembed")
def unembed(params, cfg: ModelConfig, h):
    if cfg.tie_embeddings:
        return h @ params["tok"].T
    return h @ params["unembed"]


@obs.scoped("loss")
def cross_entropy(logits, labels, cfg: ModelConfig):
    """Mean next-token CE; masks vocab-padding columns and label==-1."""
    vp = logits.shape[-1]
    col_mask = jnp.arange(vp) < cfg.vocab_size
    logits = jnp.where(col_mask, logits.astype(jnp.float32), -1e30)
    logz = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(
        logits, jnp.maximum(labels, 0)[..., None], axis=-1)[..., 0]
    nll = logz - gold
    valid = (labels >= 0).astype(jnp.float32)
    return jnp.sum(nll * valid) / jnp.maximum(jnp.sum(valid), 1.0)
