"""Pallas TPU kernel: Mamba2 SSD chunked scan (forward).

One (batch, head) pair per grid row; the chunk axis is the innermost grid
dimension, so the recurrent state S (n x p) stays resident in VMEM scratch
across chunk iterations — the inter-chunk linear recurrence never touches
HBM. Per chunk the kernel computes the intra-chunk masked CB^T decay matmul
(the "dual" attention form), adds the carried-state contribution, and
updates S.

VMEM working set per step (chunk=256, n=128, p=64, f32):
  x (256x64) + B,C (256x128) + scores (256x256) + S (128x64) ≈ 0.6 MB.
MXU work is the (256x128)@(128x256) CB product and (256x256)@(256x64)
score-x product — both 128-aligned.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import resolve_interpret


def _ssd_kernel(x_ref, dt_ref, a_ref, b_ref, c_ref, d_ref, y_ref, final_ref,
                state_ref, *, chunk: int, nstate: int, headdim: int):
    # note: outputs (y_ref, final_ref) precede scratch (state_ref)
    ci = pl.program_id(1)

    @pl.when(ci == 0)
    def init():
        state_ref[...] = jnp.zeros_like(state_ref)

    x = x_ref[0].astype(jnp.float32)          # (Q, p)
    dt = dt_ref[0].astype(jnp.float32)        # (Q, 1)
    A = a_ref[0].astype(jnp.float32)          # (1, 1)
    B = b_ref[0].astype(jnp.float32)          # (Q, n)
    C = c_ref[0].astype(jnp.float32)          # (Q, n)
    D = d_ref[0].astype(jnp.float32)          # (1, 1)

    ii = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    jj = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    causal = ii >= jj

    dA = dt * A                               # (Q, 1)
    # inclusive prefix sum as a masked row reduction: Mosaic has no cumsum
    # lowering, and an f32 VPU sum keeps full precision (an MXU matmul
    # against the mask would round dA to bf16 under default precision)
    seg = jnp.sum(jnp.where(causal, dA.T, 0.0), axis=1,
                  keepdims=True)              # (Q, 1)
    xdt = x * dt                              # (Q, p)

    # intra-chunk: masked decayed CB^T
    CB = C @ B.T                              # (Q, Q)
    diff = jnp.where(causal, seg - seg.T, -jnp.inf)   # seg_i - seg_j
    y = (CB * jnp.exp(diff)) @ xdt            # (Q, p)

    # carried-state contribution
    S = state_ref[...]                        # (n, p)
    y = y + jnp.exp(seg) * (C @ S)

    # state update
    seg_last = seg[chunk - 1:chunk, :]        # (1, 1)
    decay_to_end = jnp.exp(seg_last - seg)    # (Q, 1)
    state_ref[...] = S * jnp.exp(seg_last) + B.T @ (xdt * decay_to_end)

    y_ref[0] = (y + D * x).astype(y_ref.dtype)

    @pl.when(ci == pl.num_programs(1) - 1)
    def fin():
        final_ref[0] = state_ref[...].astype(final_ref.dtype)


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def ssd_scan(x, dt, A, B, C, D, *, chunk: int = 256,
             interpret: Optional[bool] = None):
    """x: (b, s, h, p)  dt: (b, s, h)  A, D: (h,)  B, C: (b, s, n)
    -> (y: (b, s, h, p), final_state: (b, h, n, p)).

    s must be a multiple of ``chunk`` (ops.py pads).
    """
    b, s, h, p = x.shape
    n = B.shape[-1]
    assert s % chunk == 0
    nc = s // chunk
    bh = b * h
    # lay out (b*h, s, ...) with B/C broadcast over heads
    xr = x.transpose(0, 2, 1, 3).reshape(bh, s, p)
    dtr = dt.transpose(0, 2, 1).reshape(bh, s, 1)
    Br = jnp.broadcast_to(B[:, None], (b, h, s, n)).reshape(bh, s, n)
    Cr = jnp.broadcast_to(C[:, None], (b, h, s, n)).reshape(bh, s, n)
    Ar = jnp.broadcast_to(A[None], (b, h)).reshape(bh, 1, 1)
    Dr = jnp.broadcast_to(D[None], (b, h)).reshape(bh, 1, 1)

    grid = (bh, nc)
    kern = functools.partial(_ssd_kernel, chunk=chunk, nstate=n, headdim=p)
    y, final = pl.pallas_call(
        kern,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, chunk, p), lambda g, i: (g, i, 0)),
            pl.BlockSpec((1, chunk, 1), lambda g, i: (g, i, 0)),
            pl.BlockSpec((1, 1, 1), lambda g, i: (g, 0, 0)),
            pl.BlockSpec((1, chunk, n), lambda g, i: (g, i, 0)),
            pl.BlockSpec((1, chunk, n), lambda g, i: (g, i, 0)),
            pl.BlockSpec((1, 1, 1), lambda g, i: (g, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, chunk, p), lambda g, i: (g, i, 0)),
            pl.BlockSpec((1, n, p), lambda g, i: (g, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, s, p), x.dtype),
            jax.ShapeDtypeStruct((bh, n, p), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((n, p), jnp.float32)],
        interpret=resolve_interpret(interpret),
    )(xr, dtr, Ar, Br, Cr, Dr)
    y = y.reshape(b, h, s, p).transpose(0, 2, 1, 3)
    final = final.reshape(b, h, n, p)
    return y, final
