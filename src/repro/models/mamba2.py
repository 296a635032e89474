"""Mamba2 / SSD (state-space duality) language model [arXiv:2405.21060].

The SSD forward pass is the chunked "dual" form: intra-chunk work is a masked
attention-like matmul (quadratic in the chunk length only), computed for all
chunks at once, as is each chunk's own state; only the linear recurrence over
the per-chunk states is scanned with ``lax.scan``.
Decode is the O(1)-per-token recurrent form — this is why mamba2 runs the
``long_500k`` shape that quadratic-attention models cannot.

Training and prefill take the Pallas kernels of ``repro.kernels.ssd``
where ``repro.kernels.ssd_kernel_applies`` (one TPU chip, chunks of a
multiple of 128 rows), and ``ssd_chunked`` everywhere else: on the CPU, on
a step over several devices and for other shapes. ``ssd_chunked`` is the
kernels' oracle in tests; ``repro.kernels.ref.ref_ssd``, the per-token
recurrence, is the oracle of both.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro import kernels, obs
from repro.kernels import ops as kops
from repro.models import layers as L
from repro.models import transformer as T
from repro.models.base import ModelConfig

# Mamba-2's dt init range (mamba_ssm Mamba2: dt_min, dt_max, dt_init_floor)
DT_MIN, DT_MAX, DT_FLOOR = 1e-3, 1e-1, 1e-4


# ---------------------------------------------------------------------------
# causal depthwise conv1d
# ---------------------------------------------------------------------------


def causal_conv(x, w, bias, state=None):
    """x: (b, s, c); w: (W, c) depthwise; bias: (c,). state: (b, W-1, c)
    carried inputs. Returns (silu(conv + bias), new_state)."""
    W = w.shape[0]
    if state is None:
        state = jnp.zeros((x.shape[0], W - 1, x.shape[2]), x.dtype)
    xp = jnp.concatenate([state, x], axis=1)
    out = bias + sum(
        w[i] * jax.lax.dynamic_slice_in_dim(xp, i, x.shape[1], axis=1)
        for i in range(W))
    new_state = xp[:, -(W - 1):]
    return jax.nn.silu(out), new_state


# ---------------------------------------------------------------------------
# SSD core (chunked dual form)
# ---------------------------------------------------------------------------


@obs.scoped("core")
def ssd_chunked(x, dt, A, B, C, D, chunk: int, initial_state=None):
    """x: (b,s,h,p)  dt: (b,s,h) (post-softplus)  A: (h,) (negative)
    B, C: (b,s,n)  D: (h,). Returns (y: (b,s,h,p), final_state: (b,h,n,p)).

    Every chunk's intra-chunk output and state are batched einsums over all
    chunks at once; only the recurrence over the (b,h,n,p) chunk states is
    a ``lax.scan``. Per-head tensors keep the chunk axis minor,
    (b,nc,h,p,Q): a chunk of x is one transpose of its (Q, h*p) block, so
    splitting h*p into heads never leaves p (64) as the minor axis, which
    would pad to 128 lanes on a TPU."""
    b, s, h, p = x.shape
    n = B.shape[-1]
    pad = (-s) % chunk
    if pad:
        x = jnp.pad(x, ((0, 0), (0, pad), (0, 0), (0, 0)))
        dt = jnp.pad(dt, ((0, 0), (0, pad), (0, 0)))
        B = jnp.pad(B, ((0, 0), (0, pad), (0, 0)))
        C = jnp.pad(C, ((0, 0), (0, pad), (0, 0)))
    nc = x.shape[1] // chunk

    def to_chunks(t):                           # (b,s,...) -> (b,nc,Q,...)
        return t.astype(jnp.float32).reshape((b, nc, chunk) + t.shape[2:])

    xt = to_chunks(x).reshape(b, nc, chunk, h * p).swapaxes(2, 3)
    xt = xt.reshape(b, nc, h, p, chunk)                       # (b,nc,h,p,Q)
    dtt = to_chunks(dt).swapaxes(2, 3)                        # (b,nc,h,Q)
    Bc, Cc = to_chunks(B), to_chunks(C)                       # (b,nc,Q,n)
    Af = A.astype(jnp.float32)

    if initial_state is None:
        initial_state = jnp.zeros((b, h, n, p), jnp.float32)

    seg = jnp.cumsum(dtt * Af[:, None], axis=-1)              # (b,nc,h,Q)
    seg_last = seg[..., -1:]                                  # (b,nc,h,1)
    xdt = xt * dtt[:, :, :, None]                             # (b,nc,h,p,Q)

    # diagonal blocks: C B^T is shared by the heads (one group). Mask the
    # exponent BEFORE exp: for i<j, seg_i - seg_j > 0 overflows.
    idx = jnp.arange(chunk)
    causal = idx[:, None] >= idx[None, :]
    CB = jnp.einsum("bcin,bcjn->bcij", Cc, Bc)                # (b,nc,Q,Q)
    diff = jnp.where(causal, seg[..., :, None] - seg[..., None, :],
                     -jnp.inf)                                # (b,nc,h,Q,Q)
    scores = CB[:, :, None] * jnp.exp(diff)
    y = jnp.einsum("bchij,bchpj->bchpi", scores, xdt)

    # each chunk's own state, decayed to the chunk's end
    states = jnp.einsum("bcjn,bchpj->cbhnp", Bc,
                        xdt * jnp.exp(seg_last - seg)[:, :, :, None])

    # state passing, the only sequential part: S_c = a_c S_{c-1} + states_c
    def pass_state(S, inp):
        a, st = inp                             # (b,h) (b,h,n,p)
        return S * a[..., None, None] + st, S

    chunk_decay = jnp.exp(seg_last[..., 0]).transpose(1, 0, 2)   # (nc,b,h)
    final_state, S_in = jax.lax.scan(pass_state, initial_state,
                                     (chunk_decay, states))

    # off-diagonal blocks: the state each chunk starts from
    y = y + (jnp.einsum("bcin,cbhnp->bchpi", Cc, S_in)
             * jnp.exp(seg)[:, :, :, None])
    y = y + D.astype(jnp.float32)[:, None, None] * xt
    y = y.reshape(b, nc, h * p, chunk).swapaxes(2, 3)
    y = y.reshape(b, nc * chunk, h, p)[:, :s]
    return y.astype(x.dtype), final_state


@obs.scoped("core")
def ssd_kernel(x, dt, A, B, C, D, chunk: int, initial_state=None):
    """``ssd_chunked``'s contract through the Pallas kernels
    (``repro.kernels.ops.ssd``), under the same scope."""
    return kops.ssd(x, dt, A, B, C, D, chunk=chunk,
                   initial_state=initial_state)


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


def init_dt_bias(rng, nh: int, dtype):
    """Mamba-2's dt init: dt log-uniform in [DT_MIN, DT_MAX], floored at
    DT_FLOOR, stored as its inverse softplus so softplus(dt_bias) = dt."""
    lo, hi = jnp.log(DT_MIN), jnp.log(DT_MAX)
    dt = jnp.exp(jax.random.uniform(rng, (nh,), minval=lo, maxval=hi))
    dt = jnp.maximum(dt, DT_FLOOR)
    return (dt + jnp.log(-jnp.expm1(-dt))).astype(dtype)


def init_mamba_block(rng, cfg: ModelConfig, d_model: Optional[int] = None):
    d = d_model or cfg.d_model
    di, nh, n = cfg.d_inner, cfg.ssm_nheads, cfg.ssm_state
    W = cfg.ssm_conv_width
    ks = jax.random.split(rng, 12)

    def conv_bias(k, c):   # torch Conv1d's default: U(+-1/sqrt(fan_in))
        return jax.random.uniform(k, (c,), minval=-W ** -0.5,
                                  maxval=W ** -0.5).astype(cfg.dtype)

    return {
        "ln": {"scale": jnp.ones((d,), cfg.dtype)},
        "wz": L.dense_init(ks[0], d, di, cfg.dtype),
        "wx": L.dense_init(ks[1], d, di, cfg.dtype),
        "wB": L.dense_init(ks[2], d, n, cfg.dtype),
        "wC": L.dense_init(ks[3], d, n, cfg.dtype),
        "wdt": L.dense_init(ks[4], d, nh, cfg.dtype),
        "dt_bias": init_dt_bias(ks[9], nh, cfg.dtype),
        "A_log": jnp.log(jax.random.uniform(ks[5], (nh,), minval=1.0,
                                            maxval=16.0)).astype(cfg.dtype),
        "D": jnp.ones((nh,), cfg.dtype),
        "conv_x": (jax.random.normal(ks[6], (W, di)) * W ** -0.5).astype(cfg.dtype),
        "conv_BC": (jax.random.normal(ks[7], (W, 2 * n)) * W ** -0.5).astype(cfg.dtype),
        "conv_x_bias": conv_bias(ks[10], di),
        "conv_BC_bias": conv_bias(ks[11], 2 * n),
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
    x, new_cx = causal_conv(x, bp["conv_x"], bp["conv_x_bias"], cx)
    BC, new_cbc = causal_conv(BC, bp["conv_BC"], bp["conv_BC_bias"], cbc)
    B, C = jnp.split(BC, 2, axis=-1)

    x = x.reshape(b, s, nh, p)
    s0 = cache["ssm"] if cache is not None else None
    if kernels.ssd_kernel_applies(cfg.ssm_chunk, nh, p):
        obs.count("ssd.kernel")
        core = ssd_kernel
    else:
        obs.count("ssd.chunked")
        core = ssd_chunked
    y, S = core(x, dt, A, B, C, bp["D"], cfg.ssm_chunk, initial_state=s0)
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

    x, new_cx = causal_conv(x, bp["conv_x"], bp["conv_x_bias"],
                            cache["conv_x"])
    BC, new_cbc = causal_conv(BC, bp["conv_BC"], bp["conv_BC_bias"],
                              cache["conv_BC"])
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
