"""Mamba2 / SSD (state-space duality) language model [arXiv:2405.21060].

The SSD forward pass is the chunked "dual" form: intra-chunk work is a masked
attention-like matmul (quadratic in the chunk length only), inter-chunk work
is a linear recurrence over per-chunk states, scanned with ``lax.scan``.
Decode is the O(1)-per-token recurrent form — this is why mamba2 runs the
``long_500k`` shape that quadratic-attention models cannot.

``repro.kernels.ssd_scan`` provides the Pallas TPU kernel for the chunk body;
this module is the pure-jnp reference implementation used on CPU and as the
kernel oracle.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro import obs
from repro.models import layers as L
from repro.models import transformer as T
from repro.models.base import ModelConfig


# ---------------------------------------------------------------------------
# causal depthwise conv1d
# ---------------------------------------------------------------------------


def causal_conv(x, w, state=None):
    """x: (b, s, c); w: (W, c) depthwise. state: (b, W-1, c) carried inputs.
    Returns (out, new_state)."""
    W = w.shape[0]
    if state is None:
        state = jnp.zeros((x.shape[0], W - 1, x.shape[2]), x.dtype)
    xp = jnp.concatenate([state, x], axis=1)
    out = sum(w[i] * jax.lax.dynamic_slice_in_dim(xp, i, x.shape[1], axis=1)
              for i in range(W))
    new_state = xp[:, -(W - 1):]
    return jax.nn.silu(out), new_state


# ---------------------------------------------------------------------------
# SSD core (chunked dual form)
# ---------------------------------------------------------------------------


@obs.scoped("core")
def ssd_chunked(x, dt, A, B, C, D, chunk: int, initial_state=None):
    """x: (b,s,h,p)  dt: (b,s,h) (post-softplus)  A: (h,) (negative)
    B, C: (b,s,n)  D: (h,). Returns (y: (b,s,h,p), final_state: (b,h,n,p))."""
    b, s, h, p = x.shape
    n = B.shape[-1]
    pad = (-s) % chunk
    if pad:
        x = jnp.pad(x, ((0, 0), (0, pad), (0, 0), (0, 0)))
        dt = jnp.pad(dt, ((0, 0), (0, pad), (0, 0)))
        B = jnp.pad(B, ((0, 0), (0, pad), (0, 0)))
        C = jnp.pad(C, ((0, 0), (0, pad), (0, 0)))
    nc = x.shape[1] // chunk

    def to_chunks(t):
        return t.reshape((b, nc, chunk) + t.shape[2:]).swapaxes(0, 1)

    xc, dtc, Bc, Cc = map(to_chunks, (x.astype(jnp.float32),
                                      dt.astype(jnp.float32),
                                      B.astype(jnp.float32),
                                      C.astype(jnp.float32)))
    Af = A.astype(jnp.float32)

    if initial_state is None:
        initial_state = jnp.zeros((b, h, n, p), jnp.float32)

    idx = jnp.arange(chunk)
    causal = idx[:, None] >= idx[None, :]

    def chunk_body(S, xs):
        x_c, dt_c, B_c, C_c = xs               # (b,Q,h,p) (b,Q,h) (b,Q,n)
        dA = dt_c * Af                          # (b,Q,h)
        seg = jnp.cumsum(dA, axis=1)            # (b,Q,h)
        xdt = x_c * dt_c[..., None]
        # intra-chunk: attention-like masked matmul
        CB = jnp.einsum("bin,bjn->bij", C_c, B_c)
        # mask the exponent BEFORE exp: for i<j, seg_i - seg_j > 0 overflows
        diff = jnp.where(causal[None, :, :, None],
                         seg[:, :, None, :] - seg[:, None, :, :], -jnp.inf)
        scores = CB[..., None] * jnp.exp(diff)                    # (b,Q,Q,h)
        y = jnp.einsum("bijh,bjhp->bihp", scores, xdt)
        # inter-chunk: contribution of the carried state
        y = y + jnp.einsum("bin,bhnp->bihp", C_c, S) * jnp.exp(seg)[..., None]
        # state update
        seg_last = seg[:, -1, :]                # (b,h)
        Bx = jnp.einsum("bjn,bjhp->bhnp",
                        B_c, xdt * jnp.exp(seg_last[:, None] - seg)[..., None])
        S = S * jnp.exp(seg_last)[:, :, None, None] + Bx
        return S, y

    final_state, yc = jax.lax.scan(chunk_body, initial_state,
                                   (xc, dtc, Bc, Cc))
    y = yc.swapaxes(0, 1).reshape(b, nc * chunk, h, p)[:, :s]
    y = y + D.astype(jnp.float32)[None, None, :, None] * x[:, :s].astype(jnp.float32)
    return y.astype(x.dtype), final_state


@obs.scoped("core")
def ssd_decode_step(S, x, dt, A, B, C, D):
    """One-token recurrence. x: (b,h,p)  dt: (b,h)  B, C: (b,n)  S: (b,h,n,p)."""
    xf, dtf = x.astype(jnp.float32), dt.astype(jnp.float32)
    dA = jnp.exp(dtf * A.astype(jnp.float32))            # (b,h)
    Bx = jnp.einsum("bn,bhp->bhnp", B.astype(jnp.float32),
                    xf * dtf[..., None])
    S = S * dA[..., None, None] + Bx
    y = jnp.einsum("bn,bhnp->bhp", C.astype(jnp.float32), S)
    y = y + D.astype(jnp.float32)[None, :, None] * xf
    return y.astype(x.dtype), S


# ---------------------------------------------------------------------------
# mamba2 block
# ---------------------------------------------------------------------------


def init_mamba_block(rng, cfg: ModelConfig, d_model: Optional[int] = None):
    d = d_model or cfg.d_model
    di, nh, n = cfg.d_inner, cfg.ssm_nheads, cfg.ssm_state
    W = cfg.ssm_conv_width
    ks = jax.random.split(rng, 9)
    return {
        "ln": {"scale": jnp.ones((d,), cfg.dtype)},
        "wz": L.dense_init(ks[0], d, di, cfg.dtype),
        "wx": L.dense_init(ks[1], d, di, cfg.dtype),
        "wB": L.dense_init(ks[2], d, n, cfg.dtype),
        "wC": L.dense_init(ks[3], d, n, cfg.dtype),
        "wdt": L.dense_init(ks[4], d, nh, cfg.dtype),
        "dt_bias": jnp.zeros((nh,), cfg.dtype),
        "A_log": jnp.log(jax.random.uniform(ks[5], (nh,), minval=1.0,
                                            maxval=16.0)).astype(cfg.dtype),
        "D": jnp.ones((nh,), cfg.dtype),
        "conv_x": (jax.random.normal(ks[6], (W, di)) * W ** -0.5).astype(cfg.dtype),
        "conv_BC": (jax.random.normal(ks[7], (W, 2 * n)) * W ** -0.5).astype(cfg.dtype),
        "gate_ln": {"scale": jnp.ones((di,), cfg.dtype)},
        "wo": L.dense_init(ks[8], di, d, cfg.dtype),
    }


@obs.scoped("ssd")
def apply_mamba_block(bp, cfg: ModelConfig, h, cache=None):
    """cache: {"conv_x", "conv_BC", "ssm"} or None. Returns (out, new_cache)."""
    b, s, d = h.shape
    nh, p, n = cfg.ssm_nheads, cfg.ssm_headdim, cfg.ssm_state
    hin = L.rmsnorm_raw(h, bp["ln"]["scale"])
    z = hin @ bp["wz"]
    x = hin @ bp["wx"]
    BC = jnp.concatenate([hin @ bp["wB"], hin @ bp["wC"]], axis=-1)
    dt = jax.nn.softplus((hin @ bp["wdt"]).astype(jnp.float32)
                         + bp["dt_bias"].astype(jnp.float32))
    A = -jnp.exp(bp["A_log"].astype(jnp.float32))

    cx = cache["conv_x"] if cache is not None else None
    cbc = cache["conv_BC"] if cache is not None else None
    x, new_cx = causal_conv(x, bp["conv_x"], cx)
    BC, new_cbc = causal_conv(BC, bp["conv_BC"], cbc)
    B, C = jnp.split(BC, 2, axis=-1)

    x = x.reshape(b, s, nh, p)
    s0 = cache["ssm"] if cache is not None else None
    if cfg.use_ssd_kernel and s0 is None:
        # Pallas SSD chunk-scan kernel (train/prefill-from-scratch path)
        from repro.kernels import ops as kops
        with jax.named_scope("core"):
            y, S = kops.ssd_scan(x, dt, A, B, C, bp["D"],
                                 chunk=min(cfg.ssm_chunk, s))
    else:
        y, S = ssd_chunked(x, dt, A, B, C, bp["D"], cfg.ssm_chunk,
                           initial_state=s0)
    y = y.reshape(b, s, nh * p)
    y = L.rmsnorm_raw(y * jax.nn.silu(z), bp["gate_ln"]["scale"])
    out = y @ bp["wo"]
    new_cache = {"conv_x": new_cx, "conv_BC": new_cbc, "ssm": S}
    return h + out, new_cache


@obs.scoped("ssd")
def apply_mamba_decode(bp, cfg: ModelConfig, h, cache):
    """Single-token path (s == 1) using the recurrent form."""
    b, s, d = h.shape
    nh, p = cfg.ssm_nheads, cfg.ssm_headdim
    hin = L.rmsnorm_raw(h, bp["ln"]["scale"])
    z = hin @ bp["wz"]
    x = hin @ bp["wx"]
    BC = jnp.concatenate([hin @ bp["wB"], hin @ bp["wC"]], axis=-1)
    dt = jax.nn.softplus((hin @ bp["wdt"]).astype(jnp.float32)
                         + bp["dt_bias"].astype(jnp.float32))
    A = -jnp.exp(bp["A_log"].astype(jnp.float32))

    x, new_cx = causal_conv(x, bp["conv_x"], cache["conv_x"])
    BC, new_cbc = causal_conv(BC, bp["conv_BC"], cache["conv_BC"])
    B, C = jnp.split(BC, 2, axis=-1)

    y, S = ssd_decode_step(cache["ssm"], x[:, 0].reshape(b, nh, p),
                           dt[:, 0], A, B[:, 0], C[:, 0], bp["D"])
    y = y.reshape(b, 1, nh * p)
    y = L.rmsnorm_raw(y * jax.nn.silu(z), bp["gate_ln"]["scale"])
    new_cache = {"conv_x": new_cx, "conv_BC": new_cbc, "ssm": S}
    return h + y @ bp["wo"], new_cache


def init_block_cache(cfg: ModelConfig, batch: int):
    W, di, n = cfg.ssm_conv_width, cfg.d_inner, cfg.ssm_state
    return {
        "conv_x": jnp.zeros((batch, W - 1, di), cfg.dtype),
        "conv_BC": jnp.zeros((batch, W - 1, 2 * n), cfg.dtype),
        "ssm": jnp.zeros((batch, cfg.ssm_nheads, n, cfg.ssm_headdim),
                         jnp.float32),
    }


# ---------------------------------------------------------------------------
# full model
# ---------------------------------------------------------------------------


def init(rng, cfg: ModelConfig):
    ks = jax.random.split(rng, 2)
    return {
        "embed": L.init_embed(ks[0], cfg),
        "blocks": T.stack_init(lambda k: init_mamba_block(k, cfg), ks[1],
                               cfg.n_layers),
        "final_norm": L.init_norm(cfg),
    }


def forward(params, cfg: ModelConfig, tokens, *, cache=None, decode=False):
    h = L.embed_tokens(params["embed"], tokens)

    def body(h, xs):
        bp, c = xs
        if not decode:
            h = T.seq_constraint(cfg, h)
        if decode:
            h, nc = apply_mamba_decode(bp, cfg, h, c)
        else:
            h, nc = apply_mamba_block(bp, cfg, h, cache=c)
        return h, nc

    body = T.remat_wrap(cfg, body)
    h, new_cache = jax.lax.scan(body, h, (params["blocks"], cache))
    h = L.apply_norm(params["final_norm"], cfg, h)
    return L.unembed(params["embed"], cfg, h), new_cache


def loss_fn(params, cfg: ModelConfig, batch):
    logits, _ = forward(params, cfg, batch["tokens"])
    return L.cross_entropy(logits[:, :-1], batch["labels"][:, 1:], cfg)


def init_cache(cfg: ModelConfig, batch: int, max_seq: int = 0):
    c = init_block_cache(cfg, batch)
    return jax.tree.map(
        lambda x: jnp.broadcast_to(x[None], (cfg.n_layers,) + x.shape), c)


def prefill(params, cfg: ModelConfig, tokens, max_seq: Optional[int] = None):
    b, _ = tokens.shape
    cache = init_cache(cfg, b)
    logits, cache = forward(params, cfg, tokens, cache=cache)
    return logits, cache


def decode_step(params, cfg: ModelConfig, cache, pos, tokens):
    logits, cache = forward(params, cfg, tokens, cache=cache, decode=True)
    return logits, cache
