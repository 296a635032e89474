"""Hierarchical model synchronization on JAX collectives (paper Section 3.3).

The paper's ScatterReduce dataflow (Fig. 5) maps 1:1 onto TPU collectives:

  shard generator  + upload     ->  reduce-scatter  (lax.psum_scatter)
  shard aggregator (mean)       ->  (the reduction inside psum_scatter) / n
  re-upload + global aggregator ->  all-gather      (lax.all_gather)

The centralized-PS pattern of Siren/Cirrus — every worker downloads every
other worker's full gradient — maps to all-gather of *unreduced* gradients
followed by a local mean: O(n*|G|) bytes per worker instead of O(|G|).

A 2-level variant maps SMLT's hierarchy onto a multi-pod mesh: reduce-scatter
intra-pod (fast ICI), all-reduce of the small shards across pods (slow DCI),
all-gather intra-pod. All functions run inside ``shard_map``.
"""
from __future__ import annotations

from typing import Callable

import jax
import jax.numpy as jnp
from jax.sharding import Mesh
from jax.sharding import PartitionSpec as P

from repro import obs

STRATEGIES = ("allreduce", "hier", "hier2", "hier2_q", "ps")


def _flat_pad(g, n: int):
    flat = g.reshape(-1)
    pad = (-flat.shape[0]) % n
    if pad:
        flat = jnp.pad(flat, (0, pad))
    return flat, pad


def allreduce_mean(grads, axis: str, n: int):
    """Baseline: plain all-reduce mean (what XLA would emit for DP)."""
    return jax.tree.map(lambda g: jax.lax.psum(g, axis) / n, grads)


def ps_mean(grads, axis: str, n: int):
    """Siren/Cirrus centralized-store pattern: every worker gathers all
    n full gradients, then averages locally. O(n*|G|) ingress per worker."""

    def one(g):
        allg = jax.lax.all_gather(g, axis)          # (n, ...) on every worker
        return jnp.mean(allg, axis=0)

    return jax.tree.map(one, grads)


def scatter_reduce_mean(grads, axis: str, n: int):
    """SMLT hierarchical synchronization == reduce-scatter + all-gather."""

    def one(g):
        flat, pad = _flat_pad(g, n)
        shard = jax.lax.psum_scatter(flat, axis, scatter_dimension=0,
                                     tiled=True) / n
        full = jax.lax.all_gather(shard, axis, axis=0, tiled=True)
        if pad:
            full = full[:flat.shape[0] - pad]
        return full.reshape(g.shape)

    return jax.tree.map(one, grads)


def two_level_mean(grads, inner_axis: str, outer_axis: str, n_inner: int,
                   n_outer: int, *, compress_cross_pod: bool = False):
    """Pod-aware SMLT hierarchy: RS intra-pod, AR of shards across pods,
    AG intra-pod. Cross-pod traffic shrinks from |G| to |G|/n_inner per
    device pair — the TPU analogue of SMLT's shard-aggregator tree.

    ``compress_cross_pod`` additionally casts the (already intra-pod
    reduced) shard to bf16 for the slow cross-pod hop — a beyond-paper
    optimization halving DCI bytes; the intra-pod math stays full
    precision (see EXPERIMENTS.md §Perf C7 for the error analysis)."""

    def one(g):
        flat, pad = _flat_pad(g, n_inner)
        shard = jax.lax.psum_scatter(flat, inner_axis, scatter_dimension=0,
                                     tiled=True)
        if compress_cross_pod and shard.dtype == jnp.float32:
            shard = jax.lax.psum(shard.astype(jnp.bfloat16), outer_axis)
            shard = shard.astype(jnp.float32) / (n_inner * n_outer)
        else:
            shard = jax.lax.psum(shard, outer_axis) / (n_inner * n_outer)
        full = jax.lax.all_gather(shard, inner_axis, axis=0, tiled=True)
        if pad:
            full = full[:flat.shape[0] - pad]
        return full.reshape(g.shape)

    return jax.tree.map(one, grads)


@obs.scoped("grad_sync")
def sync_grads(grads, strategy: str, *, data_axis: str = "data",
               pod_axis: str = "pod", n_data: int = 1, n_pod: int = 1):
    """Dispatch on strategy name (inside shard_map over the data/pod axes)."""
    if strategy == "allreduce":
        if n_pod > 1:
            grads = jax.tree.map(lambda g: jax.lax.psum(g, pod_axis), grads)
            return allreduce_mean(grads, data_axis, n_data * n_pod)
        return allreduce_mean(grads, data_axis, n_data)
    if strategy == "hier":
        if n_pod > 1:
            return two_level_mean(grads, data_axis, pod_axis, n_data, n_pod)
        return scatter_reduce_mean(grads, data_axis, n_data)
    if strategy == "hier2":
        assert n_pod > 1, "hier2 needs a pod axis"
        return two_level_mean(grads, data_axis, pod_axis, n_data, n_pod)
    if strategy == "hier2_q":
        assert n_pod > 1, "hier2_q needs a pod axis"
        return two_level_mean(grads, data_axis, pod_axis, n_data, n_pod,
                              compress_cross_pod=True)
    if strategy == "ps":
        if n_pod > 1:
            grads = jax.tree.map(lambda g: jax.lax.psum(g, pod_axis) / n_pod,
                                 grads)
        return ps_mean(grads, data_axis, n_data)
    raise ValueError(f"unknown strategy {strategy!r}; one of {STRATEGIES}")


def make_sync_grad_fn(loss_fn: Callable, mesh: Mesh, strategy: str,
                      *, data_axis: str = "data", pod_axis: str = "pod"):
    """Build f(params, batch) -> (loss, synced_grads) where per-worker grads
    are computed on the local batch slice and synchronized with ``strategy``.
    Params replicated; batch sharded on axis 0 over data (x pod) axes.
    """
    axes = dict(mesh.shape)
    n_data = axes.get(data_axis, 1)
    n_pod = axes.get(pod_axis, 1)
    batch_axes = ((pod_axis, data_axis) if n_pod > 1 else (data_axis,))

    def local_step(params, batch):
        loss, grads = jax.value_and_grad(loss_fn)(params, batch)
        grads = sync_grads(grads, strategy, data_axis=data_axis,
                           pod_axis=pod_axis, n_data=n_data, n_pod=n_pod)
        loss = jax.lax.pmean(loss, data_axis)
        if n_pod > 1:
            loss = jax.lax.pmean(loss, pod_axis)
        return loss, grads

    return jax.shard_map(
        local_step, mesh=mesh,
        in_specs=(P(), P(batch_axes)),
        out_specs=(P(), P()), check_vma=False)
