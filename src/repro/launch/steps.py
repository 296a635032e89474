"""Jitted step builders for the production meshes.

The SMLT synchronization strategy is a first-class knob of ``train_step``:

  "allreduce" — gradients kept replicated over the data axes; XLA emits a
                flat all-reduce (the naive baseline).
  "hier"      — SMLT's hierarchical ScatterReduce: gradients are sharded
                over ``data`` (reduce-scatter), optimizer state lives
                sharded (each "worker" owns its shard — the paper's shard
                aggregator), and updated params are all-gathered. On the
                multi-pod mesh the RS/AG stay *intra-pod* and only the
                |G|/16-sized shards cross pods — the 2-level hierarchy.
  "hier1"     — flat 1-level variant over (pod, data) jointly, for the
                §Perf comparison against the 2-level schedule.

The centralized-PS baseline (Siren/Cirrus) is intentionally NOT lowered at
production scale — its O(n|G|) per-device gather is the pattern the paper
(and our Fig-7/8 benchmarks + shard_map semantic path) show to be
non-viable; see benchmarks/comm_scaling.py.
"""
from __future__ import annotations

from typing import Optional

import jax
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P

from repro import obs
from repro.distributed.sharding import (batch_specs, cache_specs, named,
                                        param_specs)
from repro.launch.mesh import axis_size, data_axes, data_size
from repro.models import registry
from repro.models.base import ModelConfig
from repro.optim.adamw import AdamW, AdamWState


def _grad_axes(mesh, strategy: str):
    if strategy == "hier":
        return "data"
    if strategy == "hier1":
        return data_axes(mesh)
    if strategy == "allreduce":
        return None
    raise ValueError(f"unknown train sync strategy {strategy!r}")


def make_train_step(cfg: ModelConfig, mesh, *, strategy: str = "hier",
                    fsdp: bool = False, optimizer: Optional[AdamW] = None,
                    donate: bool = True):
    """-> (jitted step, params_shardings, opt_shardings, batch_shardings).

    step(params, opt_state, batch) -> (params, opt_state, loss)
    """
    opt = optimizer or AdamW(lr=3e-4)
    model_n = axis_size(mesh, "model")
    daxes = data_axes(mesh)
    dsize = data_size(mesh)
    gaxes = _grad_axes(mesh, strategy)
    rng = jax.random.key(0)
    pshapes = jax.eval_shape(lambda k: registry.init(k, cfg), rng)

    # FSDP spans ALL data-like axes (pod x data) so 512-chip ZeRO really
    # divides the optimizer state by 32, not 16
    pspecs = param_specs(pshapes, model_size=model_n,
                         fsdp_axis=(daxes if fsdp else None),
                         fsdp_divisor=dsize)
    # ZeRO-style placement for the hier strategies: gradients constrained
    # to the reduce-scatter layout...
    zspecs = (param_specs(pshapes, model_size=model_n, fsdp_axis=gaxes,
                          fsdp_min_size=2 ** 14,
                          fsdp_divisor=(dsize if strategy == "hier1"
                                        else axis_size(mesh, "data")))
              if gaxes else pspecs)
    # ...while the optimizer STATE always spans all data-like axes (the
    # cross-pod re-scatter of already-reduced G/16 shards is cheap, and
    # mu+nu must divide by 32 on the 512-chip mesh to fit HBM)
    ospecs_base = (param_specs(pshapes, model_size=model_n, fsdp_axis=daxes,
                               fsdp_min_size=2 ** 14, fsdp_divisor=dsize)
                   if gaxes else pspecs)
    ospecs = AdamWState(step=P(), mu=ospecs_base, nu=ospecs_base)

    def train_step(params, opt_state, batch):
        loss, grads = jax.value_and_grad(
            lambda p: registry.loss_fn(p, cfg, batch))(params)
        if gaxes:
            with jax.named_scope("grad_sync"):
                grads = jax.lax.with_sharding_constraint(
                    grads, named(mesh, zspecs))
        new_params, new_state = opt.update(grads, opt_state, params)
        return new_params, new_state, loss

    def batch_shardings(batch_shapes):
        return named(mesh, batch_specs(batch_shapes, daxes, data_size=dsize))

    pshard = named(mesh, pspecs)
    oshard = named(mesh, ospecs)
    obs.build("train_step")
    jit_step = jax.jit(
        train_step,
        in_shardings=None,  # taken from arguments at lower time
        out_shardings=(pshard, oshard, NamedSharding(mesh, P())),
        donate_argnums=(0, 1) if donate else ())
    return jit_step, pshard, oshard, batch_shardings


def make_prefill_step(cfg: ModelConfig, mesh, *, fsdp: bool = False):
    """step(params, batch) -> (logits, cache)."""
    model_n = axis_size(mesh, "model")
    daxes = data_axes(mesh)
    dsize = data_size(mesh)
    rng = jax.random.key(0)
    pshapes = jax.eval_shape(lambda k: registry.init(k, cfg), rng)
    pspecs = param_specs(pshapes, model_size=model_n,
                         fsdp_axis=(daxes if fsdp else None),
                         fsdp_divisor=dsize)

    def prefill_step(params, batch):
        return registry.prefill(params, cfg, batch)

    def batch_shardings(batch_shapes):
        return named(mesh, batch_specs(batch_shapes, daxes, data_size=dsize))

    return jax.jit(prefill_step), named(mesh, pspecs), batch_shardings


def make_serve_step(cfg: ModelConfig, mesh, *, fsdp: bool = False):
    """step(params, cache, pos, tokens) -> (logits, cache) — ONE new token
    against a seq_len KV/SSM cache."""
    model_n = axis_size(mesh, "model")
    daxes = data_axes(mesh)
    dsize = data_size(mesh)
    rng = jax.random.key(0)
    pshapes = jax.eval_shape(lambda k: registry.init(k, cfg), rng)
    pspecs = param_specs(pshapes, model_size=model_n,
                         fsdp_axis=(daxes if fsdp else None),
                         fsdp_divisor=dsize)

    def decode_step(params, cache, pos, tokens):
        return registry.decode_step(params, cfg, cache, pos, tokens)

    def cache_shardings(cache_shapes):
        return named(mesh, cache_specs(cache_shapes, daxes,
                                       model_size=model_n, data_size=dsize))

    def batch_shardings(batch_shapes):
        return named(mesh, batch_specs(batch_shapes, daxes, data_size=dsize))

    return (jax.jit(decode_step), named(mesh, pspecs), cache_shardings,
            batch_shardings)


def decode_cache_shapes(cfg: ModelConfig, batch: int, max_seq: int,
                        extras_shapes=None):
    """ShapeDtypeStructs of the decode cache (params never materialized)."""
    rng = jax.random.key(0)
    pshapes = jax.eval_shape(lambda k: registry.init(k, cfg), rng)

    def build(params, extras):
        return registry.init_decode_cache(params, cfg, batch, max_seq,
                                          batch_extras=extras)

    return jax.eval_shape(build, pshapes, extras_shapes)
