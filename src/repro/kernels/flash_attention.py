"""Pallas TPU kernel: causal flash attention (forward).

Used for the 32k prefill shapes: O(seq^2) attention without materializing
the score matrix in HBM. Online-softmax accumulation in VMEM scratch; the
grid is (batch*heads, q_blocks, k_blocks) with the k axis innermost so the
(m, l, acc) running state lives in VMEM across k iterations. Fully-masked
k-blocks (k_start > q_end under the causal/sliding-window mask) are skipped
with @pl.when — the same block-sparsity the dense models rely on for the
sliding-window long-context variant.

MXU alignment: block_q x head_dim and block_k x head_dim tiles at 128
multiples; scores computed in f32.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import resolve_interpret

NEG_INF = -1e30


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
                  block_q: int, block_k: int, seq_k: int, causal: bool,
                  window: int, scale: float):
    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q_start = qi * block_q
    k_start = ki * block_k
    # skip blocks fully above the causal diagonal / outside the window
    run = jnp.bool_(True)
    if causal:
        run = jnp.logical_and(run, k_start <= q_start + block_q - 1)
    if window:
        run = jnp.logical_and(run,
                              k_start + block_k - 1 >= q_start - window + 1)

    @pl.when(run)
    def body():
        q = q_ref[0].astype(jnp.float32) * scale        # (bq, d)
        k = k_ref[0].astype(jnp.float32)                # (bk, d)
        v = v_ref[0].astype(jnp.float32)
        s = q @ k.T                                     # (bq, bk)
        qpos = q_start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
        kpos = k_start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        mask = jnp.ones_like(s, dtype=jnp.bool_)
        if causal:
            mask = qpos >= kpos
        if window:
            mask = jnp.logical_and(mask, qpos - kpos < window)
        s = jnp.where(mask, s, NEG_INF)

        m_prev = m_ref[...]
        l_prev = l_ref[...]
        m_cur = jnp.max(s, axis=1)[:, None]
        m_new = jnp.maximum(m_prev, m_cur)
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_new = alpha * l_prev + jnp.sum(p, axis=1)[:, None]
        acc_ref[...] = acc_ref[...] * alpha + p @ v
        m_ref[...] = m_new
        l_ref[...] = l_new

    @pl.when(ki == pl.num_programs(2) - 1)
    def finalize():
        o_ref[0] = (acc_ref[...]
                    / jnp.maximum(l_ref[...], 1e-30)).astype(o_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("causal", "window", "block_q", "block_k",
                              "interpret"))
def flash_attention(q, k, v, *, causal: bool = True, window: int = 0,
                    block_q: int = 256, block_k: int = 256,
                    interpret: Optional[bool] = None):
    """q: (b, h, sq, d); k, v: (b, h, sk, d) -> (b, h, sq, d).

    seq lengths must be multiples of the block sizes (ops.py pads).
    """
    b, h, sq, d = q.shape
    sk = k.shape[2]
    assert sq % block_q == 0 and sk % block_k == 0
    bh = b * h
    qf = q.reshape(bh, sq, d)
    kf = k.reshape(bh, sk, d)
    vf = v.reshape(bh, sk, d)
    grid = (bh, sq // block_q, sk // block_k)
    kern = functools.partial(
        _flash_kernel, block_q=block_q, block_k=block_k, seq_k=sk,
        causal=causal, window=window, scale=d ** -0.5)
    out = pl.pallas_call(
        kern,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda g, i, j: (g, i, 0)),
            pl.BlockSpec((1, block_k, d), lambda g, i, j: (g, j, 0)),
            pl.BlockSpec((1, block_k, d), lambda g, i, j: (g, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_q, d), lambda g, i, j: (g, i, 0)),
        out_shape=jax.ShapeDtypeStruct((bh, sq, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, 1), jnp.float32),   # running max m
            pltpu.VMEM((block_q, 1), jnp.float32),   # running denom l
            pltpu.VMEM((block_q, d), jnp.float32),   # output accumulator
        ],
        interpret=resolve_interpret(interpret),
        name="flash_attention",
    )(qf, kf, vf)
    return out.reshape(b, h, sq, d)
