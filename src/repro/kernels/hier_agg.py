"""Pallas TPU kernel: sharded gradient aggregation (the paper's hot loop).

The shard aggregator (Fig. 5, step 3) computes the mean of its assigned
shard across all n workers. On TPU this is the per-device compute inside
the reduce-scatter: each device reduces an (n_workers, shard_len) tile it
received. The kernel tiles shard_len into VMEM-resident blocks (the worker
axis stays whole — n is small), accumulates in f32, and optionally fuses
the SGD update (aggregate + apply) so gradients never round-trip to HBM
between aggregation and the optimizer — an SMLT-specific fusion: the paper's
'global aggregator reconstructs the updated model' step.

Block size: (n, 8, 1024) f32 tiles keep the working set << 16 MB VMEM while
keeping the lane dimension at the 128-multiple the VPU wants.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import resolve_interpret


def _agg_kernel(shards_ref, out_ref, *, n_workers: int):
    acc = shards_ref[0].astype(jnp.float32)
    for w in range(1, n_workers):
        acc = acc + shards_ref[w].astype(jnp.float32)
    out_ref[...] = (acc / n_workers).astype(out_ref.dtype)


def _agg_apply_kernel(shards_ref, param_ref, out_ref, *, n_workers: int,
                      lr: float):
    acc = shards_ref[0].astype(jnp.float32)
    for w in range(1, n_workers):
        acc = acc + shards_ref[w].astype(jnp.float32)
    g = acc / n_workers
    out_ref[...] = (param_ref[...].astype(jnp.float32) - lr * g).astype(
        out_ref.dtype)


def _grid_and_specs(n_workers: int, length: int, block: int):
    assert length % block == 0, (length, block)
    grid = (length // block,)
    in_spec = pl.BlockSpec((n_workers, block), lambda i: (0, i))
    out_spec = pl.BlockSpec((block,), lambda i: (i,))
    return grid, in_spec, out_spec


@functools.partial(jax.jit, static_argnames=("block", "interpret"))
def aggregate_shards(shards: jax.Array, *, block: int = 8 * 1024,
                     interpret: Optional[bool] = None) -> jax.Array:
    """shards: (n_workers, shard_len) -> (shard_len,) mean.

    shard_len must be a multiple of ``block`` (ops.py pads).
    """
    n, length = shards.shape
    grid, in_spec, out_spec = _grid_and_specs(n, length, block)
    return pl.pallas_call(
        functools.partial(_agg_kernel, n_workers=n),
        grid=grid,
        in_specs=[in_spec],
        out_specs=out_spec,
        out_shape=jax.ShapeDtypeStruct((length,), shards.dtype),
        interpret=resolve_interpret(interpret),
        name="hier_agg",
    )(shards)


@functools.partial(jax.jit,
                   static_argnames=("lr", "block", "interpret"))
def aggregate_and_apply(shards: jax.Array, param_shard: jax.Array, *,
                        lr: float, block: int = 8 * 1024,
                        interpret: Optional[bool] = None) -> jax.Array:
    """Fused mean-aggregate + SGD apply on the owned shard.
    shards: (n_workers, shard_len); param_shard: (shard_len,)."""
    n, length = shards.shape
    grid, in_spec, out_spec = _grid_and_specs(n, length, block)
    return pl.pallas_call(
        functools.partial(_agg_apply_kernel, n_workers=n, lr=lr),
        grid=grid,
        in_specs=[in_spec, pl.BlockSpec((block,), lambda i: (i,))],
        out_specs=out_spec,
        out_shape=jax.ShapeDtypeStruct((length,), param_shard.dtype),
        interpret=resolve_interpret(interpret),
        name="hier_agg_apply",
    )(shards, param_shard)
