"""Pallas TPU kernels: causal flash attention, forward and backward.

Scores and probabilities live only in VMEM tiles; nothing of size
``seq x seq`` reaches HBM. Three kernels, each named for the device trace:

- ``flash_attention`` (forward): grid (batch*heads, q blocks, k blocks),
  k innermost, an online softmax whose running max, denominator and output
  accumulator stay in VMEM scratch across the k steps. Besides the output
  it writes the rows' log-sum-exp ``lse``, which the backward reuses.
- ``flash_attention_dkv``: grid (batch*heads, k blocks, q blocks); for one
  key tile it loops over the query tiles, rebuilds the probabilities from
  ``lse`` and accumulates ``dk`` and ``dv``.
- ``flash_attention_dq``: grid (batch*heads, q blocks, k blocks); for one
  query tile it loops over the key tiles and accumulates ``dq``.

Both backward kernels take ``di = sum(o * do)`` per row, computed once.

Masks: causal (query position >= key position) and a sliding window
(query - key < window). A tile with no visible pair is skipped, and its
index map is clamped to the nearest tile that runs, so that a skipped grid
step fetches no new block. Only tiles that straddle a mask edge build the
mask.

Products: on the TPU every MXU product takes bfloat16 operands cast from
the float32 tiles, with float32 accumulation -- the one bfloat16 pass that
XLA's default precision gives the jnp path. The Pallas interpreter runs on
the CPU, whose default products are float32, so interpreted kernels keep
float32 operands and agree with the jnp path on the same platform.
Softmax statistics and accumulators are float32 everywhere.

``lse`` and ``di`` are float32 arrays of shape (batch*heads, seq, 128), each
row's value replicated over the 128 lanes a TPU block's minor dimension
needs.
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
LANES = 128
_NT = (((1,), (1,)), ((), ()))      # a @ b.T


# ---------------------------------------------------------------------------
# masks and tile skipping (shared by the three kernels)
# ---------------------------------------------------------------------------


def _runs(q_start, k_start, block_q, block_k, causal, window):
    """Whether the (q, k) tile holds at least one visible pair."""
    run = jnp.bool_(True)
    if causal:
        run = jnp.logical_and(run, k_start <= q_start + block_q - 1)
    if window:
        run = jnp.logical_and(run, q_start - (k_start + block_k - 1) < window)
    return run


def _edge(q_start, k_start, block_q, block_k, causal, window):
    """Whether the (q, k) tile holds a masked pair, so it needs the mask."""
    edge = jnp.bool_(False)
    if causal:
        edge = jnp.logical_or(edge, k_start + block_k - 1 > q_start)
    if window:
        edge = jnp.logical_or(edge,
                              q_start + block_q - 1 - k_start >= window)
    return edge


def _masked(s, q_start, k_start, causal, window):
    qpos = q_start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
    kpos = k_start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    mask = jnp.ones(s.shape, jnp.bool_)
    if causal:
        mask = qpos >= kpos
    if window:
        mask = jnp.logical_and(mask, qpos - kpos < window)
    return jnp.where(mask, s, NEG_INF)


def _k_range(qi, block_q, block_k, n_k, causal, window):
    """First and last key block that query block ``qi`` sees."""
    q_start = qi * block_q
    lo = jnp.maximum((q_start - window + 1) // block_k, 0) if window else 0
    hi = (q_start + block_q - 1) // block_k if causal else n_k - 1
    return lo, hi


def _kv_map(block_q, block_k, n_k, causal, window):
    """Index map of k and v on a (bh, q blocks, k blocks) grid: a skipped k
    step stays on the nearest block that query block ``i`` sees."""
    def index(g, i, j):
        lo, hi = _k_range(i, block_q, block_k, n_k, causal, window)
        return g, _clamp(j, lo, hi), 0
    return index


def _q_range(ki, block_q, block_k, n_q, causal, window):
    """First and last query block that sees key block ``ki``."""
    k_start = ki * block_k
    lo = k_start // block_q if causal else 0
    hi = (jnp.minimum((k_start + block_k + window - 2) // block_q, n_q - 1)
          if window else n_q - 1)
    return lo, hi


def _clamp(i, lo, hi):
    return jnp.minimum(jnp.maximum(i, lo), hi)


def _tile(body, q_start, k_start, block_q, block_k, causal, window):
    """Run ``body(masked)`` on a visible tile, masking only edge tiles."""
    run = _runs(q_start, k_start, block_q, block_k, causal, window)
    edge = _edge(q_start, k_start, block_q, block_k, causal, window)

    @pl.when(jnp.logical_and(run, edge))
    def _():
        body(True)

    @pl.when(jnp.logical_and(run, jnp.logical_not(edge)))
    def _():
        body(False)


def _operand_dtype(interpret: bool):
    return jnp.float32 if interpret else jnp.bfloat16


def _scores(q_ref, k_ref, q_start, k_start, masked, *, scale, mxu, causal,
            window):
    """(scaled q in the product dtype, k in it, masked f32 scores)."""
    q = (q_ref[...].astype(jnp.float32) * scale).astype(mxu)
    k = k_ref[...].astype(mxu)
    s = jax.lax.dot_general(q, k, _NT, preferred_element_type=jnp.float32)
    if masked:
        s = _masked(s, q_start, k_start, causal, window)
    return q, k, s


# the innermost grid axis carries the VMEM accumulators from step to step
_PARAMS = pltpu.CompilerParams(
    dimension_semantics=("parallel", "parallel", "arbitrary"))


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------


def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, m_ref, l_ref, acc_ref,
                *, block_q, block_k, causal, window, scale, mxu):
    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q_start = qi * block_q
    k_start = ki * block_k

    def body(masked):
        _, _, s = _scores(q_ref, k_ref, q_start, k_start, masked,
                          scale=scale, mxu=mxu, causal=causal, window=window)
        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1)[:, None])
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_ref[...] = alpha * l_ref[...] + jnp.sum(p, axis=1)[:, None]
        acc_ref[...] = acc_ref[...] * alpha + jnp.dot(
            p.astype(mxu), v_ref[...].astype(mxu),
            preferred_element_type=jnp.float32)
        m_ref[...] = m_new

    _tile(body, q_start, k_start, block_q, block_k, causal, window)

    @pl.when(ki == pl.num_programs(2) - 1)
    def finalize():
        l = jnp.maximum(l_ref[...], 1e-30)
        o_ref[...] = (acc_ref[...] / l).astype(o_ref.dtype)
        lse_ref[...] = jnp.broadcast_to(m_ref[...] + jnp.log(l),
                                        lse_ref.shape)


def flash_forward(q, k, v, *, causal: bool, window: int, block_q: int,
                  block_k: int, interpret: Optional[bool] = None):
    """q: (bh, sq, d); k, v: (bh, sk, d) -> (o (bh, sq, d), lse (bh, sq, 128)).

    Sequence lengths must be multiples of the block sizes (``ops`` pads).
    """
    bh, sq, d = q.shape
    sk = k.shape[1]
    assert sq % block_q == 0 and sk % block_k == 0
    interpret = resolve_interpret(interpret)
    kv_map = _kv_map(block_q, block_k, sk // block_k, causal, window)
    kern = functools.partial(
        _fwd_kernel, block_q=block_q, block_k=block_k, causal=causal,
        window=window, scale=d ** -0.5, mxu=_operand_dtype(interpret))
    return pl.pallas_call(
        kern,
        grid=(bh, sq // block_q, sk // block_k),
        in_specs=[
            pl.BlockSpec((None, block_q, d), lambda g, i, j: (g, i, 0)),
            pl.BlockSpec((None, block_k, d), kv_map),
            pl.BlockSpec((None, block_k, d), kv_map),
        ],
        out_specs=[
            pl.BlockSpec((None, block_q, d), lambda g, i, j: (g, i, 0)),
            pl.BlockSpec((None, block_q, LANES), lambda g, i, j: (g, i, 0)),
        ],
        out_shape=[jax.ShapeDtypeStruct((bh, sq, d), q.dtype),
                   jax.ShapeDtypeStruct((bh, sq, LANES), jnp.float32)],
        scratch_shapes=[
            pltpu.VMEM((block_q, 1), jnp.float32),   # running max m
            pltpu.VMEM((block_q, 1), jnp.float32),   # running denom l
            pltpu.VMEM((block_q, d), jnp.float32),   # output accumulator
        ],
        compiler_params=_PARAMS,
        interpret=interpret,
        name="flash_attention",
    )(q, k, v)


# ---------------------------------------------------------------------------
# backward
# ---------------------------------------------------------------------------


def _probs_and_ds(q_ref, k_ref, v_ref, do_ref, lse_ref, di_ref, q_start,
                  k_start, masked, *, scale, mxu, causal, window):
    """Rebuild one tile's probabilities and the scores' gradient ds."""
    q, k, s = _scores(q_ref, k_ref, q_start, k_start, masked, scale=scale,
                      mxu=mxu, causal=causal, window=window)
    p = jnp.exp(s - lse_ref[:, :1])
    do = do_ref[...].astype(mxu)
    dp = jax.lax.dot_general(do, v_ref[...].astype(mxu), _NT,
                             preferred_element_type=jnp.float32)
    ds = p * (dp - di_ref[:, :1])
    return q, k, do, p, ds


def _dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, di_ref, dk_ref, dv_ref,
                dk_acc, dv_acc, *, block_q, block_k, causal, window, scale,
                mxu):
    ki = pl.program_id(1)
    qi = pl.program_id(2)

    @pl.when(qi == 0)
    def init():
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)

    q_start = qi * block_q
    k_start = ki * block_k

    def body(masked):
        q, _, do, p, ds = _probs_and_ds(
            q_ref, k_ref, v_ref, do_ref, lse_ref, di_ref, q_start, k_start,
            masked, scale=scale, mxu=mxu, causal=causal, window=window)
        dv_acc[...] += jnp.dot(p.T.astype(mxu), do,
                               preferred_element_type=jnp.float32)
        # q is already scaled: dk = ds^T (scale q)
        dk_acc[...] += jnp.dot(ds.T.astype(mxu), q,
                               preferred_element_type=jnp.float32)

    _tile(body, q_start, k_start, block_q, block_k, causal, window)

    @pl.when(qi == pl.num_programs(2) - 1)
    def finalize():
        dk_ref[...] = dk_acc[...].astype(dk_ref.dtype)
        dv_ref[...] = dv_acc[...].astype(dv_ref.dtype)


def _dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, di_ref, dq_ref, dq_acc,
               *, block_q, block_k, causal, window, scale, mxu):
    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def init():
        dq_acc[...] = jnp.zeros_like(dq_acc)

    q_start = qi * block_q
    k_start = ki * block_k

    def body(masked):
        _, k, _, _, ds = _probs_and_ds(
            q_ref, k_ref, v_ref, do_ref, lse_ref, di_ref, q_start, k_start,
            masked, scale=scale, mxu=mxu, causal=causal, window=window)
        dq_acc[...] += jnp.dot(ds.astype(mxu), k,
                               preferred_element_type=jnp.float32)

    _tile(body, q_start, k_start, block_q, block_k, causal, window)

    @pl.when(ki == pl.num_programs(2) - 1)
    def finalize():
        dq_ref[...] = (dq_acc[...] * scale).astype(dq_ref.dtype)


def flash_backward(q, k, v, o, lse, do, *, causal: bool, window: int,
                   block_q: int, block_k: int,
                   interpret: Optional[bool] = None):
    """Gradients (dq, dk, dv) of ``flash_forward``'s output, given its
    output ``o``, its ``lse`` and the output's cotangent ``do``."""
    bh, sq, d = q.shape
    sk = k.shape[1]
    interpret = resolve_interpret(interpret)
    n_q, n_k = sq // block_q, sk // block_k
    di = jnp.sum(o.astype(jnp.float32) * do.astype(jnp.float32), axis=-1)
    di = jnp.broadcast_to(di[..., None], (bh, sq, LANES))
    kw = dict(block_q=block_q, block_k=block_k, causal=causal, window=window,
              scale=d ** -0.5, mxu=_operand_dtype(interpret))

    # dk, dv: grid (bh, k blocks, q blocks); skipped q tiles are clamped
    def q_map(g, j, i):
        lo, hi = _q_range(j, block_q, block_k, n_q, causal, window)
        return g, _clamp(i, lo, hi), 0

    q_spec = pl.BlockSpec((None, block_q, d), q_map)
    row_spec = pl.BlockSpec((None, block_q, LANES), q_map)
    kv_spec = pl.BlockSpec((None, block_k, d), lambda g, j, i: (g, j, 0))
    dk, dv = pl.pallas_call(
        functools.partial(_dkv_kernel, **kw),
        grid=(bh, n_k, n_q),
        in_specs=[q_spec, kv_spec, kv_spec, q_spec, row_spec, row_spec],
        out_specs=[kv_spec, kv_spec],
        out_shape=[jax.ShapeDtypeStruct(k.shape, k.dtype),
                   jax.ShapeDtypeStruct(v.shape, v.dtype)],
        scratch_shapes=[pltpu.VMEM((block_k, d), jnp.float32),
                        pltpu.VMEM((block_k, d), jnp.float32)],
        compiler_params=_PARAMS,
        interpret=interpret,
        name="flash_attention_dkv",
    )(q, k, v, do, lse, di)

    # dq: grid (bh, q blocks, k blocks), as the forward's
    q_spec = pl.BlockSpec((None, block_q, d), lambda g, i, j: (g, i, 0))
    row_spec = pl.BlockSpec((None, block_q, LANES), lambda g, i, j: (g, i, 0))
    kv_spec = pl.BlockSpec((None, block_k, d),
                           _kv_map(block_q, block_k, n_k, causal, window))
    dq = pl.pallas_call(
        functools.partial(_dq_kernel, **kw),
        grid=(bh, n_q, n_k),
        in_specs=[q_spec, kv_spec, kv_spec, q_spec, row_spec, row_spec],
        out_specs=q_spec,
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        scratch_shapes=[pltpu.VMEM((block_q, d), jnp.float32)],
        compiler_params=_PARAMS,
        interpret=interpret,
        name="flash_attention_dq",
    )(q, k, v, do, lse, di)
    return dq, dk, dv
