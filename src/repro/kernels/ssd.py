"""Pallas TPU kernels: Mamba-2's SSD core, forward and backward.

The chunked dual form of ``models.mamba2.ssd_chunked``, with each chunk's
Q x Q work and the carried chunk state kept in VMEM. Two kernels, each
named for the device trace:

- ``ssd_chunk_fwd``: grid (batch, head block, chunk), chunks innermost and
  in order. The (n, hb*p) float32 state of the head block is carried in
  VMEM scratch from chunk to chunk. Per chunk it computes ``C B^T`` once
  for the block's hb heads, builds each head's masked decay from ``seg``,
  writes y = diagonal block + off-diagonal term from the carried state +
  ``D x``, and updates the state. Besides y and the final state it
  writes, where it is differentiated, the state each chunk starts from,
  which the backward reads.
- ``ssd_chunk_bwd``: grid (batch, chunk, head block), chunks in reverse.
  The state's gradient for every head block is carried in VMEM scratch.
  ``C B^T`` is computed once per (batch, chunk), at its first head block,
  and the gradient of ``C B^T``, ``dB`` and ``dC`` accumulate over the
  head blocks in scratch. It writes dx, d``dt``, d``seg``, ``dB``,
  ``dC``, per-chunk partial sums of d``D`` and the initial state's
  gradient.

Layouts: x, y, dy and dx are (b, s, h*p) -- the model's own layout, no
transpose -- read as (Q, hb*p) blocks, head j of a block in lanes
[j*p, (j+1)*p). ``dt`` and ``seg`` (the within-chunk cumulative sum of
``dt * A``, computed outside) are (b, h, s), read as (hb, Q) blocks. B and
C are (b, s, n). A state is (b, h/hb, n, hb*p): head j's (n, p) state in
the same lanes as its x. ``D`` is expanded to one value per lane, (1, h*p).

Products: on the TPU every MXU product takes bfloat16 operands cast from
float32 tiles, with float32 accumulation -- the one bfloat16 pass XLA's
default precision gives ``ssd_chunked``'s float32 einsums. Interpreted
kernels (the CPU) keep float32 operands. Decays, sums, states and
accumulators are float32 everywhere.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import resolve_interpret

_NT = (((1,), (1,)), ((), ()))      # a @ b.T
_TN = (((0,), (0,)), ((), ()))      # a.T @ b
_NN = (((1,), (0,)), ((), ()))      # a @ b
F32 = jnp.float32
# the widest head block: 8 heads of 64 are 512 lanes
MAX_BLOCK_LANES = 512


def head_block(heads: int, head_dim: int) -> Optional[int]:
    """The number of heads a kernel step takes: the largest that divides
    ``heads``, fills a multiple of 128 lanes with at most
    ``MAX_BLOCK_LANES``, and is a multiple of 8 (a (hb, Q) block of
    ``seg``) or all the heads. None where no such block exists."""
    fits = [hb for hb in range(1, heads + 1)
            if heads % hb == 0 and (hb * head_dim) % 128 == 0
            and hb * head_dim <= MAX_BLOCK_LANES
            and (hb % 8 == 0 or hb == heads)]
    return max(fits) if fits else None


def _operand_dtype(interpret: bool):
    return F32 if interpret else jnp.bfloat16


def _mm(a, b, dims, mxu):
    return jax.lax.dot_general(a.astype(mxu), b.astype(mxu), dims,
                               preferred_element_type=F32)


def _causal(q: int):
    return (jax.lax.broadcasted_iota(jnp.int32, (q, q), 0)
            >= jax.lax.broadcasted_iota(jnp.int32, (q, q), 1))


def _lanes(col, p: int):
    """(hb, 1) -> (1, hb*p): head j's value in its p lanes."""
    hb = col.shape[0]
    own = (jax.lax.broadcasted_iota(jnp.int32, (hb, hb * p), 0)
           == jax.lax.broadcasted_iota(jnp.int32, (hb, hb * p), 1) // p)
    return jnp.sum(jnp.where(own, col, 0.), axis=0, keepdims=True)


def _decays(seg, p: int):
    """From a (hb, Q) block of ``seg``: (seg^T, exp(seg)^T, the decay from
    each row to the chunk's end exp(seg_last - seg)^T, all (Q, hb), and
    the chunk's whole decay exp(seg_last) per lane, (1, hb*p))."""
    q = seg.shape[1]
    seg_t = seg.T
    last = seg_t[q - 1:q, :]
    return (seg_t, jnp.exp(seg_t), jnp.exp(last - seg_t),
            _lanes(jnp.exp(seg[:, q - 1:q]), p))


def _decay(col, row, causal):
    """L[i, j] = exp(seg_i - seg_j) for i >= j, else 0. The exponent is
    masked BEFORE exp: for i < j, seg_i - seg_j > 0 can overflow."""
    return jnp.exp(jnp.where(causal, col - row, -jnp.inf))


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------


def _fwd_kernel(x_ref, dt_ref, seg_ref, b_ref, c_ref, d_ref, s0_ref,
                y_ref, sfin_ref, *refs, hb, p, mxu, residuals):
    sin_ref, s_ref, z_ref = refs if residuals else (None,) + refs
    ci = pl.program_id(2)

    @pl.when(ci == 0)
    def init():
        s_ref[...] = s0_ref[...]

    S = s_ref[...]                                  # (n, hb*p) chunk start
    if residuals:
        sin_ref[...] = S
    q = x_ref.shape[0]
    Bm, Cm = b_ref[...].astype(F32), c_ref[...].astype(F32)
    cb = _mm(Cm, Bm, _NT, mxu)                      # (Q, Q), shared by heads
    seg = seg_ref[...]                              # (hb, Q)
    seg_t, e_seg, w_end, e_last = _decays(seg, p)
    dt_t = dt_ref[...].T
    causal = _causal(q)
    x = x_ref[...].astype(F32)                      # (Q, hb*p)
    skip = d_ref[...] * x                           # D x, every head
    off = _mm(Cm, S, _NN, mxu)                      # C S, every head
    for j in range(hb):
        sl = slice(j * p, (j + 1) * p)
        xdt = x[:, sl] * dt_t[:, j:j + 1]
        m = cb * _decay(seg_t[:, j:j + 1], seg[j:j + 1, :], causal)
        y = (_mm(m, xdt, _NN, mxu) + e_seg[:, j:j + 1] * off[:, sl]
             + skip[:, sl])
        y_ref[:, sl] = y.astype(y_ref.dtype)
        z_ref[:, sl] = xdt * w_end[:, j:j + 1]      # decayed to chunk end
    s_ref[...] = S * e_last + _mm(Bm, z_ref[...], _TN, mxu)   # + B^T Z

    @pl.when(ci == pl.num_programs(2) - 1)
    def finalize():
        sfin_ref[...] = s_ref[...]


def ssd_forward(x, dt, seg, B, C, D, S0, *, chunk: int, hb: int, p: int,
                residuals: bool, interpret: Optional[bool] = None):
    """x: (b, s, h*p); dt, seg: (b, h, s) float32; B, C: (b, s, n);
    D: (1, h*p) float32; S0: (b, h/hb, n, hb*p) float32. ``s`` is a
    multiple of ``chunk``. Returns [y (b, s, h*p), final state like S0],
    and with ``residuals`` the chunk-start states the backward reads,
    (b, s/chunk, h/hb, n, hb*p): a forward that is not differentiated
    writes nothing that grows with the number of chunks."""
    b, s, hp = x.shape
    n = B.shape[-1]
    nc, G, lanes = s // chunk, hp // (hb * p), hb * p
    interpret = resolve_interpret(interpret)
    kern = functools.partial(_fwd_kernel, hb=hb, p=p, residuals=residuals,
                             mxu=_operand_dtype(interpret))
    row = pl.BlockSpec((None, chunk, lanes), lambda i, g, c: (i, c, g))
    head = pl.BlockSpec((None, hb, chunk), lambda i, g, c: (i, g, c))
    bc = pl.BlockSpec((None, chunk, n), lambda i, g, c: (i, c, 0))
    state = pl.BlockSpec((None, None, n, lanes), lambda i, g, c: (i, g, 0, 0))
    out_specs = [row, state]
    out_shape = [jax.ShapeDtypeStruct(x.shape, x.dtype),
                 jax.ShapeDtypeStruct(S0.shape, F32)]
    if residuals:
        out_specs.append(pl.BlockSpec((None, None, None, n, lanes),
                                      lambda i, g, c: (i, c, g, 0, 0)))
        out_shape.append(jax.ShapeDtypeStruct((b, nc, G, n, lanes), F32))
    return pl.pallas_call(
        kern,
        grid=(b, G, nc),
        in_specs=[row, head, head, bc, bc,
                  pl.BlockSpec((1, lanes), lambda i, g, c: (0, g)), state],
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=[pltpu.VMEM((n, lanes), F32),       # carried state
                        pltpu.VMEM((chunk, lanes), F32)],  # decayed x dt
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
        name="ssd_chunk_fwd",
    )(x, dt, seg, B, C, D, S0)


# ---------------------------------------------------------------------------
# backward
# ---------------------------------------------------------------------------


def _bwd_kernel(x_ref, dy_ref, dt_ref, seg_ref, b_ref, c_ref, d_ref, sin_ref,
                dsfin_ref, dx_ref, ddt_ref, dseg_ref, db_ref, dc_ref, dd_ref,
                ds0_ref, ds_ref, cb_ref, dcb_ref, db_acc, dc_acc, dye_ref,
                z_ref, col_ref, ddt_col_ref, *, hb, p, mxu):
    ci = pl.program_id(1)                           # reversed chunk order
    g = pl.program_id(2)
    q = x_ref.shape[0]
    Bm, Cm = b_ref[...].astype(F32), c_ref[...].astype(F32)

    @pl.when(ci == 0)
    def init_state_grad():
        ds_ref[g] = dsfin_ref[...]

    @pl.when(g == 0)
    def init_chunk():
        cb_ref[...] = _mm(Cm, Bm, _NT, mxu)
        dcb_ref[...] = jnp.zeros_like(dcb_ref)
        db_acc[...] = jnp.zeros_like(db_acc)
        dc_acc[...] = jnp.zeros_like(dc_acc)

    dS = ds_ref[g]                                  # d(state at chunk end)
    S = sin_ref[...]                                # state at chunk start
    cb = cb_ref[...]
    seg = seg_ref[...]                              # (hb, Q)
    seg_t, e_seg, w_end, e_last = _decays(seg, p)
    last = seg_t[q - 1:q, :]                        # (1, hb)
    dt_t = dt_ref[...].T
    causal = _causal(q)
    is_last = jax.lax.broadcasted_iota(jnp.int32, (q, 1), 0) == q - 1
    x = x_ref[...].astype(F32)
    dy = dy_ref[...].astype(F32)
    ddy = d_ref[...] * dy                           # D dy, every head
    off = _mm(Cm, S, _NN, mxu)                      # C S_in, every head
    dz = _mm(Bm, dS, _NN, mxu)                      # B dS, every head
    dss = dS * S
    dcb = jnp.zeros((q, q), F32)
    for j in range(hb):
        sl = slice(j * p, (j + 1) * p)
        col = seg_t[:, j:j + 1]
        dtc = dt_t[:, j:j + 1]
        xj, dyj = x[:, sl], dy[:, sl]
        xdt = xj * dtc
        # diagonal block: y = M xdt, M = CB o L
        el = _decay(col, seg[j:j + 1, :], causal)
        m = cb * el
        dm = _mm(dyj, xdt, _NT, mxu)                # dy xdt^T
        dxdt = _mm(m, dyj, _TN, mxu)                # M^T dy
        dcb = dcb + dm * el
        pm = dm * m
        dseg_ref[j:j + 1, :] = -jnp.sum(pm, axis=0, keepdims=True)
        dseg = jnp.sum(pm, axis=1, keepdims=True)
        # off-diagonal term: y += exp(seg) C S_in
        dye = dyj * e_seg[:, j:j + 1]
        dye_ref[:, sl] = dye
        dseg += jnp.sum(dye * off[:, sl], axis=1, keepdims=True)
        # state update: S_out = exp(seg_last) S_in + B^T (w xdt)
        w = w_end[:, j:j + 1]
        dzj = dz[:, sl]
        dw = jnp.sum(dzj * xdt, axis=1, keepdims=True) * w
        dxdt = dxdt + w * dzj
        z_ref[:, sl] = xdt * w
        dlast = (jnp.sum(dw, axis=0, keepdims=True) + jnp.exp(
            last[:, j:j + 1]) * jnp.sum(dss[:, sl], keepdims=True))
        col_ref[:, j:j + 1] = dseg - dw + jnp.where(is_last, dlast, 0.)
        dx_ref[:, sl] = (dxdt * dtc + ddy[:, sl]).astype(dx_ref.dtype)
        ddt_col_ref[:, j:j + 1] = jnp.sum(dxdt * xj, axis=1, keepdims=True)
    dseg_ref[...] += col_ref[...].T
    ddt_ref[...] = ddt_col_ref[...].T
    dye, z = dye_ref[...], z_ref[...]
    dS_in = e_last * dS + _mm(Cm, dye, _TN, mxu)    # + C^T (e^seg dy)
    ds_ref[g] = dS_in
    dd_ref[...] = jnp.sum(dy * x, axis=0, keepdims=True)
    dcb_ref[...] += dcb
    dc_acc[...] += _mm(dye, S, _NT, mxu)            # (e^seg dy) S_in^T
    db_acc[...] += _mm(z, dS, _NT, mxu)             # (w xdt) dS^T

    @pl.when(g == pl.num_programs(2) - 1)
    def finalize_chunk():
        dcb_all = dcb_ref[...]
        dc_ref[...] = (dc_acc[...] + _mm(dcb_all, Bm, _NN, mxu)).astype(
            dc_ref.dtype)
        db_ref[...] = (db_acc[...] + _mm(dcb_all, Cm, _TN, mxu)).astype(
            db_ref.dtype)

    @pl.when(ci == pl.num_programs(1) - 1)
    def finalize():
        ds0_ref[...] = dS_in


def ssd_backward(x, dt, seg, B, C, D, S_in, dy, dS_final, *, chunk: int,
                 hb: int, p: int, interpret: Optional[bool] = None):
    """Gradients of ``ssd_forward``'s (y, final state) given its inputs,
    its chunk-start states ``S_in`` and the cotangents ``dy`` and
    ``dS_final``. Returns (dx, ddt, dseg, dB, dC, dD (1, h*p), dS0)."""
    b, s, hp = x.shape
    n = B.shape[-1]
    nc, G, lanes = s // chunk, hp // (hb * p), hb * p
    interpret = resolve_interpret(interpret)
    kern = functools.partial(_bwd_kernel, hb=hb, p=p,
                             mxu=_operand_dtype(interpret))

    def rev(c):
        return nc - 1 - c

    row = pl.BlockSpec((None, chunk, lanes), lambda i, c, g: (i, rev(c), g))
    head = pl.BlockSpec((None, hb, chunk), lambda i, c, g: (i, g, rev(c)))
    bc = pl.BlockSpec((None, chunk, n), lambda i, c, g: (i, rev(c), 0))
    # dS_final is read at the first chunk step of each head block, dS0
    # written at the last; in between their blocks stay put, so the
    # pipeline neither fetches nor writes them back
    dsfin = pl.BlockSpec((None, None, n, lanes), lambda i, c, g: (
        i, jnp.where(c == 0, g, G - 1), 0, 0))
    ds0 = pl.BlockSpec((None, None, n, lanes), lambda i, c, g: (
        i, jnp.where(c == nc - 1, g, 0), 0, 0))
    dx, ddt, dseg, dB, dC, dD, dS0 = pl.pallas_call(
        kern,
        grid=(b, nc, G),
        in_specs=[row, row, head, head, bc, bc,
                  pl.BlockSpec((1, lanes), lambda i, c, g: (0, g)),
                  pl.BlockSpec((None, None, None, n, lanes),
                               lambda i, c, g: (i, rev(c), g, 0, 0)),
                  dsfin],
        out_specs=[row, head, head, bc, bc,
                   pl.BlockSpec((None, None, 1, lanes),
                                lambda i, c, g: (i, rev(c), 0, g)),
                   ds0],
        out_shape=[jax.ShapeDtypeStruct(x.shape, x.dtype),
                   jax.ShapeDtypeStruct(dt.shape, F32),
                   jax.ShapeDtypeStruct(seg.shape, F32),
                   jax.ShapeDtypeStruct(B.shape, B.dtype),
                   jax.ShapeDtypeStruct(C.shape, C.dtype),
                   jax.ShapeDtypeStruct((b, nc, 1, hp), F32),
                   jax.ShapeDtypeStruct(dS_final.shape, F32)],
        scratch_shapes=[
            pltpu.VMEM((G, n, lanes), F32),      # d(state), every head block
            pltpu.VMEM((chunk, chunk), F32),     # C B^T of the chunk
            pltpu.VMEM((chunk, chunk), F32),     # its gradient, all heads
            pltpu.VMEM((chunk, n), F32),         # dB of the chunk
            pltpu.VMEM((chunk, n), F32),         # dC of the chunk
            pltpu.VMEM((chunk, lanes), F32),     # exp(seg) dy
            pltpu.VMEM((chunk, lanes), F32),     # decayed x dt
            pltpu.VMEM((chunk, hb), F32),        # dseg, per-row sums
            pltpu.VMEM((chunk, hb), F32),        # ddt
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary")),
        interpret=interpret,
        name="ssd_chunk_bwd",
    )(x, dy, dt, seg, B, C, D, S_in, dS_final)
    return dx, ddt, dseg, dB, dC, jnp.sum(dD, axis=(0, 1)), dS0
