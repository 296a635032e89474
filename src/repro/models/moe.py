"""Mixture-of-Experts transformer (qwen2-moe-a2.7b, arctic-480b).

Capacity-based GShard-style dispatch (one-hot dispatch/combine einsums) so the
all-to-all pattern is explicit in the lowered HLO. Experts are stacked on a
leading E axis (sharded over the ``model`` mesh axis = expert parallelism).

 - qwen2-moe: 4 shared (always-on) experts + 60 routed top-4.
 - arctic: 128 routed top-2 + a dense residual FFN in parallel.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro import obs
from repro.models import layers as L
from repro.models import transformer as T
from repro.models.base import ModelConfig

def init_moe_ffn(rng, cfg: ModelConfig):
    ks = jax.random.split(rng, 4)
    E = cfg.n_experts
    d, f = cfg.d_model, cfg.d_ff

    def one_expert(k):
        k1, k2, k3 = jax.random.split(k, 3)
        return {"wi": L.dense_init(k1, d, f, cfg.dtype),
                "wg": L.dense_init(k2, d, f, cfg.dtype),
                "wo": L.dense_init(k3, f, d, cfg.dtype)}

    p = {"router": L.dense_init(ks[0], d, E, cfg.dtype, scale=0.02),
         "experts": jax.vmap(one_expert)(jax.random.split(ks[1], E))}
    if cfg.n_shared_experts:
        p["shared"] = L.init_mlp(ks[2], cfg, d_ff=f * cfg.n_shared_experts)
    if cfg.moe_dense_residual:
        p["dense"] = L.init_mlp(ks[3], cfg, d_ff=f)
    return p


MOE_GROUP = 4096  # GShard-style dispatch group: keeps the one-hot
                  # dispatch/combine einsums O(t * g) instead of O(t^2).
                  # 4096 is train_4k's sequence length, so it divides the
                  # token count of every train and prefill shape and no
                  # shape falls back to one whole-batch group


def _moe_group(p, cfg: ModelConfig, xt):
    """Dispatch one token group. xt: (g, d) -> (out (g, d), aux scalar)."""
    g, d = xt.shape
    E, k = cfg.n_experts, cfg.top_k
    cap = max(int(cfg.moe_capacity_factor * k * g / E), 1)

    with jax.named_scope("router"):
        logits = (xt @ p["router"]).astype(jnp.float32)          # (g, E)
        probs = jax.nn.softmax(logits, axis=-1)
        gate_vals, gate_idx = jax.lax.top_k(probs, k)             # (g, k)
        gate_vals = gate_vals / jnp.maximum(
            jnp.sum(gate_vals, axis=-1, keepdims=True), 1e-9)

        # position of each (token, expert-slot) within its expert's capacity
        onehot = jax.nn.one_hot(gate_idx, E, dtype=jnp.float32)   # (g, k, E)
        pos_in_expert = (jnp.cumsum(onehot.reshape(g * k, E), axis=0)
                         .reshape(g, k, E) - onehot)
        pos = jnp.sum(pos_in_expert * onehot, axis=-1).astype(jnp.int32)
        keep = pos < cap
        gate_vals = gate_vals * keep

        # dispatch (g, E, cap) / combine tensors
        pos_oh = jax.nn.one_hot(pos, cap, dtype=jnp.float32) * keep[..., None]
        dispatch = jnp.einsum("tke,tkc->tec", onehot, pos_oh)
        combine = jnp.einsum("tke,tkc,tk->tec", onehot, pos_oh, gate_vals)

        # GShard load-balance aux loss
        frac_tokens = jnp.mean(jnp.sum(onehot, axis=1), axis=0)   # (E,)
        frac_probs = jnp.mean(probs, axis=0)
        aux = E * jnp.sum(frac_tokens * frac_probs) * cfg.router_aux_weight

    with jax.named_scope("experts"):
        ex_in = jnp.einsum("tec,td->ecd", dispatch, xt.astype(jnp.float32))
        ex_in = ex_in.astype(xt.dtype)
        ex = p["experts"]
        hidden = jax.nn.silu(jnp.einsum("ecd,edf->ecf", ex_in, ex["wi"]))
        hidden = hidden * jnp.einsum("ecd,edf->ecf", ex_in, ex["wg"])
        ex_out = jnp.einsum("ecf,efd->ecd", hidden, ex["wo"])
        out = jnp.einsum("tec,ecd->td", combine, ex_out.astype(jnp.float32))
    return out.astype(xt.dtype), aux


@obs.scoped("moe")
def apply_moe_ffn(p, cfg: ModelConfig, x):
    """x: (b, s, d) -> (out, aux_loss). Tokens are dispatched in GShard-style
    groups so the dispatch tensors stay (g, E, C) with g <= MOE_GROUP."""
    b, s, d = x.shape
    t = b * s
    xt = x.reshape(t, d)
    g = MOE_GROUP if t % MOE_GROUP == 0 else t
    xg = xt.reshape(t // g, g, d)
    out, aux = jax.vmap(lambda xx: _moe_group(p, cfg, xx))(xg)
    out = out.reshape(b, s, d)
    aux = jnp.mean(aux)

    if "shared" in p:
        out = out + L.apply_mlp(p["shared"], cfg, x)
    if "dense" in p:
        out = out + L.apply_mlp(p["dense"], cfg, x)
    return out, aux


def init_block(rng, cfg: ModelConfig):
    ks = jax.random.split(rng, 2)
    return {"ln1": L.init_norm(cfg),
            "attn": L.init_attention(ks[0], cfg),
            "ln2": L.init_norm(cfg),
            "moe": init_moe_ffn(ks[1], cfg)}


def apply_block(bp, cfg: ModelConfig, h, *, positions=None, cache=None,
                cache_index=None):
    a, new_cache = L.apply_attention(
        bp["attn"], cfg, L.apply_norm(bp["ln1"], cfg, h),
        positions=positions, cache=cache, cache_index=cache_index)
    h = h + a
    m, aux = apply_moe_ffn(bp["moe"], cfg, L.apply_norm(bp["ln2"], cfg, h))
    return h + m, new_cache, aux


def init(rng, cfg: ModelConfig):
    ks = jax.random.split(rng, 3)
    return {
        "embed": L.init_embed(ks[0], cfg),
        "blocks": T.stack_init(lambda k: init_block(k, cfg), ks[1], cfg.n_layers),
        "final_norm": L.init_norm(cfg),
    }


def forward(params, cfg: ModelConfig, tokens, *, positions=None, cache=None,
            cache_index=None):
    h = L.embed_tokens(params["embed"], tokens)

    def body(carry, xs):
        h, aux = carry
        bp, c = xs
        h, nc, a = apply_block(bp, cfg, h, positions=positions, cache=c,
                               cache_index=cache_index)
        return (h, aux + a), nc

    body = T.remat_wrap(cfg, body)
    (h, aux), new_cache = jax.lax.scan(body, (h, 0.0),
                                       (params["blocks"], cache))
    h = L.apply_norm(params["final_norm"], cfg, h)
    return L.unembed(params["embed"], cfg, h), new_cache, aux


def loss_fn(params, cfg: ModelConfig, batch):
    logits, _, aux = forward(params, cfg, batch["tokens"])
    return L.cross_entropy(logits[:, :-1], batch["labels"][:, 1:], cfg) + aux


init_cache = T.init_cache


def prefill(params, cfg: ModelConfig, tokens, max_seq: Optional[int] = None):
    b, s = tokens.shape
    cache = init_cache(cfg, b, max_seq or s)
    logits, cache, _ = forward(params, cfg, tokens, cache=cache, cache_index=0)
    return logits, cache


def decode_step(params, cfg: ModelConfig, cache, pos, tokens):
    positions = pos + jnp.zeros((1,), jnp.int32)
    logits, cache, _ = forward(params, cfg, tokens, positions=positions,
                               cache=cache, cache_index=pos)
    return logits, cache
