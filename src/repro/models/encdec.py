"""Seamless-M4T-style encoder-decoder backbone [arXiv:2308.11596].

Transformer backbone only (per the brief): the mel-spectrogram + conv codec
frontend is a STUB — ``input_specs()`` supplies precomputed frame embeddings
(b, n_frames, d_audio). Encoder: bidirectional self-attention over projected
frames. Decoder: causal self-attention + cross-attention to encoder output.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.models import layers as L
from repro.models import transformer as T
from repro.models.base import ModelConfig


def init_enc_block(rng, cfg: ModelConfig):
    ks = jax.random.split(rng, 2)
    return {"ln1": L.init_norm(cfg),
            "attn": L.init_attention(ks[0], cfg),
            "ln2": L.init_norm(cfg),
            "mlp": L.init_mlp(ks[1], cfg)}


def init_dec_block(rng, cfg: ModelConfig):
    ks = jax.random.split(rng, 3)
    return {"ln1": L.init_norm(cfg),
            "self_attn": L.init_attention(ks[0], cfg),
            "ln_x": L.init_norm(cfg),
            "cross_attn": L.init_attention(ks[1], cfg),
            "ln2": L.init_norm(cfg),
            "mlp": L.init_mlp(ks[2], cfg)}


def init(rng, cfg: ModelConfig):
    ks = jax.random.split(rng, 5)
    return {
        "embed": L.init_embed(ks[0], cfg),
        "audio_proj": L.dense_init(ks[1], cfg.d_audio, cfg.d_model, cfg.dtype),
        "encoder": T.stack_init(lambda k: init_enc_block(k, cfg), ks[2],
                                cfg.n_encoder_layers),
        "enc_norm": L.init_norm(cfg),
        "decoder": T.stack_init(lambda k: init_dec_block(k, cfg), ks[3],
                                cfg.n_layers),
        "final_norm": L.init_norm(cfg),
    }


def encode(params, cfg: ModelConfig, audio_frames):
    """audio_frames: (b, f, d_audio) -> (b, f, d_model)."""
    h = audio_frames @ params["audio_proj"]

    def body(h, bp):
        a, _ = L.apply_attention(bp["attn"], cfg,
                                 L.apply_norm(bp["ln1"], cfg, h), causal=False)
        h = h + a
        h = h + L.apply_mlp(bp["mlp"], cfg, L.apply_norm(bp["ln2"], cfg, h))
        return h, None

    body = T.remat_wrap(cfg, body)
    h, _ = jax.lax.scan(body, h, params["encoder"])
    return L.apply_norm(params["enc_norm"], cfg, h)


def cross_kv(params, cfg: ModelConfig, enc_out):
    """Precompute stacked decoder cross-attention K/V: (L, b, f, kv, hd)."""
    b, f, _ = enc_out.shape
    hd = cfg.resolved_head_dim

    def one(dbp):
        k = (enc_out @ dbp["cross_attn"]["wk"]).reshape(b, f, cfg.n_kv_heads, hd)
        v = (enc_out @ dbp["cross_attn"]["wv"]).reshape(b, f, cfg.n_kv_heads, hd)
        return {"k": k, "v": v}

    return jax.vmap(one)(params["decoder"])


def apply_dec_block(bp, cfg: ModelConfig, h, ckv, *, positions=None,
                    cache=None, cache_index=None):
    a, new_cache = L.apply_attention(
        bp["self_attn"], cfg, L.apply_norm(bp["ln1"], cfg, h),
        positions=positions, cache=cache, cache_index=cache_index)
    h = h + a
    h = h + L.cross_attention(bp["cross_attn"], cfg,
                              L.apply_norm(bp["ln_x"], cfg, h), ckv)
    h = h + L.apply_mlp(bp["mlp"], cfg, L.apply_norm(bp["ln2"], cfg, h))
    return h, new_cache


def decode_stack(params, cfg: ModelConfig, tokens, ckv, *, positions=None,
                 cache=None, cache_index=None):
    h = L.embed_tokens(params["embed"], tokens)

    def body(h, xs):
        bp, kv, c = xs
        h, nc = apply_dec_block(bp, cfg, h, kv, positions=positions,
                                cache=c, cache_index=cache_index)
        return h, nc

    body = T.remat_wrap(cfg, body)
    h, new_cache = jax.lax.scan(body, h, (params["decoder"], ckv, cache))
    h = L.apply_norm(params["final_norm"], cfg, h)
    return L.unembed(params["embed"], cfg, h), new_cache


def loss_fn(params, cfg: ModelConfig, batch):
    enc_out = encode(params, cfg, batch["audio_frames"])
    ckv = cross_kv(params, cfg, enc_out)
    logits, _ = decode_stack(params, cfg, batch["tokens"], ckv)
    return L.cross_entropy(logits[:, :-1], batch["labels"][:, 1:], cfg)


def init_self_cache(cfg: ModelConfig, batch: int, max_seq: int):
    c = L.init_kv_cache(cfg, batch, max_seq)
    return jax.tree.map(
        lambda x: jnp.broadcast_to(x[None], (cfg.n_layers,) + x.shape), c)


def prefill(params, cfg: ModelConfig, tokens, audio_frames,
            max_seq: Optional[int] = None):
    b, s = tokens.shape
    enc_out = encode(params, cfg, audio_frames)
    ckv = cross_kv(params, cfg, enc_out)
    cache = init_self_cache(cfg, b, max_seq or s)
    logits, cache = decode_stack(params, cfg, tokens, ckv, cache=cache,
                                 cache_index=0)
    return logits, {"self": cache, "cross_kv": ckv}


def decode_step(params, cfg: ModelConfig, cache, pos, tokens):
    positions = pos + jnp.zeros((1,), jnp.int32)
    logits, new_self = decode_stack(params, cfg, tokens, cache["cross_kv"],
                                    positions=positions, cache=cache["self"],
                                    cache_index=pos)
    return logits, {"self": new_self, "cross_kv": cache["cross_kv"]}
