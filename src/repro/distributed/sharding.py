"""Parameter / activation / cache sharding rules for the production meshes.

Rules are path+shape driven and uniform across the model zoo:

 - tensor parallelism over the ``model`` axis: attention head dims, FFN
   hidden dims, MoE expert axis (expert parallelism), SSM head/inner dims,
   vocab dim of embed/unembed;
 - batch over ``data`` (x ``pod`` on the multi-pod mesh);
 - optional FSDP (ZeRO-3-style) over ``data`` for weight storage — the
   paper's hierarchical "shard the state, gather on demand" insight applied
   to parameters (used for the big decode configs and the ``hier`` training
   strategy's optimizer state).

Each rule lists candidate dim assignments in preference order; the first
whose dims all divide evenly by the mesh axis wins (e.g. qwen2-moe's 60
experts don't divide a 16-way model axis, so expert parallelism falls back
to per-expert FFN tensor parallelism). Stacked-layer leaves (under
blocks/encoder/decoder/cross) keep their leading layer axis unsharded.
"""
from __future__ import annotations

import re
from typing import Optional

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding
from jax.sharding import PartitionSpec as P

# path-regex -> list of candidate {dim-from-right: axis} assignments
_RULES = [
    (r"embed/tok$",        [{-2: "model"}, {-1: "model"}]),   # (V, d)
    (r"embed/unembed$",    [{-1: "model"}]),                  # (d, V)
    (r"attn/w[qkv]$|self_attn/w[qkv]$|cross_attn/w[qkv]$", [{-1: "model"}]),
    (r"attn/wo$|self_attn/wo$|cross_attn/wo$", [{-2: "model"}]),
    (r"attn/b[qkv]$",      [{-1: "model"}]),
    (r"mlp/wi$|mlp/wg$|shared/wi$|shared/wg$|dense/wi$|dense/wg$",
                           [{-1: "model"}]),
    (r"mlp/wo$|shared/wo$|dense/wo$", [{-2: "model"}]),
    # MoE: expert parallel if E divides, else per-expert tensor parallel
    (r"experts/wi$|experts/wg$", [{-3: "model"}, {-1: "model"}]),
    (r"experts/wo$",       [{-3: "model"}, {-2: "model"}]),
    (r"router$",           [{}]),
    (r"/wz$|/wx$",         [{-1: "model"}]),          # (d, d_inner)
    (r"/wdt$",             [{-1: "model"}]),          # (d, nh)
    (r"/wB$|/wC$",         [{}]),                     # small, replicated
    (r"dt_bias$|A_log$|/D$", [{-1: "model"}]),        # (nh,)
    (r"conv_x$|conv_x_bias$", [{-1: "model"}]),       # (W, d_inner), (d_inner,)
    (r"conv_BC$|conv_BC_bias$", [{}]),
    (r"gate_ln/scale$",    [{-1: "model"}]),          # (d_inner,)
    (r"blocks/wo$",        [{-2: "model"}]),          # mamba out proj
    (r"vision_proj$|audio_proj$", [{}]),
]

_STACKED = re.compile(r"^(blocks|encoder|decoder|cross)/")


def _path_str(path) -> str:
    return "/".join(str(getattr(k, "key", getattr(k, "idx", k)))
                    for k in path)


def _assign(path: str, shape, model_size: int):
    """Pick the first candidate assignment whose dims divide evenly."""
    ndim = len(shape)
    stacked = bool(_STACKED.match(path))
    for pat, cands in _RULES:
        if re.search(pat, path):
            for cand in cands:
                ok = True
                for off, _ax in cand.items():
                    i = ndim + off
                    if i < 0 or (stacked and i == 0) \
                            or shape[i] % model_size != 0:
                        ok = False
                        break
                if ok:
                    return cand, stacked
            return {}, stacked
    return {}, stacked


def _leaf_spec(path: str, shape, *, model_size: int,
               fsdp_axis: Optional[str] = None, fsdp_min_size: int = 0,
               fsdp_divisor: int = 1) -> P:
    ndim = len(shape)
    dims, stacked = _assign(path, shape, model_size)
    entries = [None] * ndim
    for off, ax in dims.items():
        entries[ndim + off] = ax
    size = int(np.prod(shape)) if shape else 1
    if fsdp_axis and size >= fsdp_min_size:
        cands = [i for i in range(1 if stacked else 0, ndim)
                 if entries[i] is None and shape[i] % fsdp_divisor == 0
                 and shape[i] >= fsdp_divisor]
        if cands:
            i = max(cands, key=lambda i: shape[i])
            entries[i] = fsdp_axis
    while entries and entries[-1] is None:
        entries.pop()
    return P(*entries)


def param_specs(params_shapes, *, model_size: int = 1,
                fsdp_axis: Optional[str] = None,
                fsdp_min_size: int = 2 ** 20, fsdp_divisor: int = 1):
    """Pytree of PartitionSpec mirroring ``params_shapes`` (from eval_shape)."""

    def f(path, leaf):
        return _leaf_spec(_path_str(path), leaf.shape, model_size=model_size,
                          fsdp_axis=fsdp_axis, fsdp_min_size=fsdp_min_size,
                          fsdp_divisor=fsdp_divisor)

    return jax.tree_util.tree_map_with_path(f, params_shapes)


def batch_specs(batch_shapes, data_axes, *, data_size: int = 1):
    """Shard dim 0 (global batch) of every input over the data(-like) axes.
    Batches that don't divide (e.g. long_500k's batch=1) stay replicated."""
    return jax.tree.map(
        lambda x: P(data_axes) if x.shape and x.shape[0] % data_size == 0
        else P(), batch_shapes)


# second entry in the "model" tuple is the fallback dim when the first
# doesn't divide the axis (e.g. kv=8 heads on a 16-way model axis -> shard
# the 128-wide head_dim instead; GSPMD handles the sharded contraction)
_CACHE_RULES = [
    (r"(^|/)[kv]$", {1: ("data",), -2: ("model", -1)}),  # (L, b, s, kv, hd)
    (r"ssm$",    {1: ("data",), 2: ("model", 3)}),       # (L, b, nh, n, p)
    (r"conv_x$", {1: ("data",), -1: ("model",)}),        # (L, b, W-1, d_in)
    (r"conv_BC$", {1: ("data",)}),
]


def cache_specs(cache_shapes, data_axes, *, model_size: int = 1,
                data_size: int = 1):
    """KV/SSM cache specs: batch over data, heads/channels over model.
    Axes that don't divide evenly are left replicated."""

    def f(path, leaf):
        p = _path_str(path)
        shape = leaf.shape
        ndim = len(shape)
        entries = [None] * ndim
        for pat, rule in _CACHE_RULES:
            if re.search(pat, p):
                for d, spec in rule.items():
                    idx = d if d >= 0 else ndim + d
                    if spec[0] == "data":
                        if shape[idx] % data_size == 0:
                            entries[idx] = data_axes
                        continue
                    # "model" with optional fallback dim
                    cands = [idx] + [c if c >= 0 else ndim + c
                                     for c in spec[1:]]
                    for c in cands:
                        if entries[c] is None and shape[c] % model_size == 0:
                            entries[c] = "model"
                            break
                break
        else:
            if ndim >= 2 and shape[1] % data_size == 0:
                entries[1] = data_axes
        while entries and entries[-1] is None:
            entries.pop()
        return P(*entries)

    return jax.tree_util.tree_map_with_path(f, cache_shapes)


def opt_state_specs(pspecs):
    """Optimizer-state specs mirror the parameter specs leaf-for-leaf."""
    from repro.optim.adamw import AdamWState
    return AdamWState(step=P(), mu=pspecs, nu=pspecs)


def named(mesh: Mesh, spec_tree):
    return jax.tree.map(
        lambda s: NamedSharding(mesh, s), spec_tree,
        is_leaf=lambda x: isinstance(x, P))
