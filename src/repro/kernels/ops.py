"""Jit'd public wrappers for the Pallas kernels: pad to block multiples,
invoke the kernel, slice back. ``interpret=None`` lets the platform pick
the mode (``repro.kernels.resolve_interpret``)."""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from repro.kernels import hier_agg as _hier
from repro.kernels import flash_attention as _flash
from repro.kernels import ssd as _ssd


def _pad_to(x, axis: int, mult: int):
    pad = (-x.shape[axis]) % mult
    if not pad:
        return x, 0
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths), pad


@functools.partial(jax.jit, static_argnames=("block", "interpret"))
def aggregate_shards(shards, *, block: int = 8 * 1024,
                     interpret: Optional[bool] = None):
    """(n_workers, L) -> (L,) mean — the paper's shard-aggregator step."""
    n, L = shards.shape
    block = min(block, max(128, L))
    x, pad = _pad_to(shards, 1, block)
    out = _hier.aggregate_shards(x, block=block, interpret=interpret)
    return out[:L]


@functools.partial(jax.jit, static_argnames=("lr", "block", "interpret"))
def aggregate_and_apply(shards, param, *, lr: float,
                        block: int = 8 * 1024,
                        interpret: Optional[bool] = None):
    n, L = shards.shape
    block = min(block, max(128, L))
    x, _ = _pad_to(shards, 1, block)
    p, _ = _pad_to(param, 0, block)
    out = _hier.aggregate_and_apply(x, p, lr=lr, block=block,
                                    interpret=interpret)
    return out[:L]


def _block(s: int) -> int:
    """The largest of 1024, 512, 256 and 128 rows that tiles ``s`` (1024 x
    1024 tiles were the fastest that fit VMEM on a v5e at train.olmo-1b's
    shape); a shorter or untiled sequence takes one block of up to 1024
    rows, padded."""
    return next((b for b in (1024, 512, 256, 128) if s % b == 0),
                min(1024, s))


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def _flash_diff(q, k, v, causal, window, block_q, block_k, interpret):
    return _flash_diff_fwd(q, k, v, causal, window, block_q, block_k,
                           interpret)[0]


def _flash_diff_fwd(q, k, v, causal, window, block_q, block_k, interpret):
    o, lse = _flash.flash_forward(q, k, v, causal=causal, window=window,
                                  block_q=block_q, block_k=block_k,
                                  interpret=interpret)
    return o, (q, k, v, o, lse)


def _flash_diff_bwd(causal, window, block_q, block_k, interpret, res, do):
    q, k, v, o, lse = res
    return _flash.flash_backward(q, k, v, o, lse, do, causal=causal,
                                 window=window, block_q=block_q,
                                 block_k=block_k, interpret=interpret)


_flash_diff.defvjp(_flash_diff_fwd, _flash_diff_bwd)


@functools.partial(jax.jit, static_argnames=("causal", "window", "block_q",
                                             "block_k", "interpret"))
def flash_attention(q, k, v, *, causal: bool = True, window: int = 0,
                    block_q: Optional[int] = None,
                    block_k: Optional[int] = None,
                    interpret: Optional[bool] = None):
    """(b, h, s, d) attention through the Pallas kernels, forward and
    backward (custom_vjp). Block sizes default to ``_block`` of each length;
    sequences are padded to block multiples and the output sliced back."""
    b, h, sq, d = q.shape
    sk = k.shape[2]
    block_q = min(block_q or _block(sq), max(16, sq))
    block_k = min(block_k or _block(sk), max(16, sk))
    qp, _ = _pad_to(q, 2, block_q)
    kp, pk = _pad_to(k, 2, block_k)
    vp, _ = _pad_to(v, 2, block_k)
    if pk and not causal:
        # padded keys would take part in every row's softmax
        raise NotImplementedError(
            "non-causal flash with padded kv not supported; pad inputs")
    sqp, skp = qp.shape[2], kp.shape[2]
    out = _flash_diff(qp.reshape(b * h, sqp, d), kp.reshape(b * h, skp, d),
                      vp.reshape(b * h, skp, d), causal, window, block_q,
                      block_k, interpret)
    return out.reshape(b, h, sqp, d)[:, :, :sq]


@functools.partial(jax.custom_vjp, nondiff_argnums=(7, 8, 9, 10))
def _ssd_diff(x, dt, seg, B, C, D, S0, chunk, hb, p, interpret):
    y, S = _ssd.ssd_forward(x, dt, seg, B, C, D, S0, chunk=chunk, hb=hb,
                            p=p, residuals=False, interpret=interpret)
    return y, S


def _ssd_diff_fwd(x, dt, seg, B, C, D, S0, chunk, hb, p, interpret):
    y, S, S_in = _ssd.ssd_forward(x, dt, seg, B, C, D, S0, chunk=chunk,
                                  hb=hb, p=p, residuals=True,
                                  interpret=interpret)
    return (y, S), (x, dt, seg, B, C, D, S_in)


def _ssd_diff_bwd(chunk, hb, p, interpret, res, cts):
    x, dt, seg, B, C, D, S_in = res
    dy, dS = cts
    return _ssd.ssd_backward(x, dt, seg, B, C, D, S_in, dy, dS, chunk=chunk,
                             hb=hb, p=p, interpret=interpret)


_ssd_diff.defvjp(_ssd_diff_fwd, _ssd_diff_bwd)


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def ssd(x, dt, A, B, C, D, *, chunk: int, initial_state=None,
        interpret: Optional[bool] = None):
    """``models.mamba2.ssd_chunked`` through the Pallas kernels, forward and
    backward (custom_vjp), with its arguments and results: x (b, s, h, p),
    dt (b, s, h), A and D (h,), B and C (b, s, n); returns (y like x, final
    state (b, h, n, p) float32). A kernel step takes the heads of one
    ``kernels.ssd.head_block``. The sequence is padded to a multiple of
    ``chunk`` with dt = 0, which leaves the state unchanged.

    Outside the kernels: the (b, h, s) copy of dt, ``seg`` (the cumulative
    sum of dt * A within each chunk; its gradient gives dt's and A's) and
    the states' (b, h/hb, n, hb*p) layout."""
    b, s, h, p = x.shape
    n = B.shape[-1]
    hb = _ssd.head_block(h, p)
    if hb is None:
        raise ValueError(f"{h} heads of {p} do not split into 128-lane "
                         "blocks")
    G = h // hb
    pad = (-s) % chunk
    if pad:
        x = jnp.pad(x, ((0, 0), (0, pad), (0, 0), (0, 0)))
        dt = jnp.pad(dt, ((0, 0), (0, pad), (0, 0)))
        B = jnp.pad(B, ((0, 0), (0, pad), (0, 0)))
        C = jnp.pad(C, ((0, 0), (0, pad), (0, 0)))
    sp = s + pad
    dt_h = dt.astype(jnp.float32).swapaxes(1, 2)                # (b, h, sp)
    seg = jnp.cumsum((dt_h * A.astype(jnp.float32)[:, None]).reshape(
        b, h, sp // chunk, chunk), axis=-1).reshape(b, h, sp)
    D_lanes = jnp.repeat(D.astype(jnp.float32), p)[None]       # (1, h*p)
    if initial_state is None:
        S0 = jnp.zeros((b, G, n, hb * p), jnp.float32)
    else:
        S0 = initial_state.astype(jnp.float32).reshape(
            b, G, hb, n, p).swapaxes(2, 3).reshape(b, G, n, hb * p)
    y, S = _ssd_diff(x.reshape(b, sp, h * p), dt_h, seg, B, C, D_lanes, S0,
                     chunk, hb, p, interpret)
    S = S.reshape(b, G, n, hb, p).swapaxes(2, 3).reshape(b, h, n, p)
    return y.reshape(b, sp, h, p)[:, :s], S
