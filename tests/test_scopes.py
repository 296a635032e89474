"""Every family's compiled steps carry the layer scopes in their op metadata.

``repro.obs.scoped`` and ``jax.named_scope`` put a layer's name into each
HLO operation's ``op_name`` (``jit(train_step)/.../attn/core/dot_general``;
under autodiff ``transpose(jvp(attn))``), which is what a device trace
shows per operation. This lowers and compiles ``train_step`` and
``decode_step`` at ``configs.reduced()`` sizes on the CPU and finds each
scope the family should have, in the forward and the backward pass.
"""
import re

import jax
import pytest

from repro.configs import ARCHS, reduced, reduced_batch
from repro.launch.steps import (decode_cache_shapes, make_serve_step,
                                make_train_step)
from repro.launch.train import make_local_mesh
from repro.models import registry
from repro.optim import AdamW

ARCH = {"dense": "olmo-1b", "moe": "qwen2-moe-a2.7b", "ssm": "mamba2-2.7b",
        "hybrid": "zamba2-7b", "vlm": "llama-3.2-vision-90b",
        "audio": "seamless-m4t-medium"}
ATTN = ("attn", "attn/core")
MIXER = {"dense": ATTN + ("mlp",),
         "moe": ATTN + ("moe", "moe/router", "moe/experts", "moe/mlp"),
         "ssm": ("ssd", "ssd/core"),
         "hybrid": ATTN + ("mlp", "ssd", "ssd/core"),
         "vlm": ATTN + ("mlp",),
         "audio": ATTN + ("mlp",)}
LAYERS = ("embed", "norm", "unembed")
TRANSFORM = re.compile(r"\w+\((.*)\)")   # transpose(jvp(attn)) -> attn


def _plain(op_name: str) -> str:
    """'/'-joined scope path with autodiff wrappers taken off each part."""
    parts = []
    for part in op_name.split("/"):
        while True:
            m = TRANSFORM.fullmatch(part)
            if not m:
                break
            part = m.group(1)
        parts.append(part)
    return "/" + "/".join(parts) + "/"


def _op_names(compiled_text: str):
    return set(re.findall(r'op_name="([^"]*)"', compiled_text))


def _has(names, scope: str, backward: bool = False) -> bool:
    return any(f"/{scope}/" in _plain(n) for n in names
               if not backward or "transpose(" in n)


def _missing(names, scopes, backward=False):
    return [s for s in scopes if not _has(names, s, backward)]


@pytest.fixture(scope="module")
def mesh():
    return make_local_mesh()


@pytest.mark.parametrize("family", sorted(ARCH))
def test_train_step_scopes(family, mesh):
    cfg = reduced(ARCHS[ARCH[family]])
    opt = AdamW(lr=1e-3)
    step, *_ = make_train_step(cfg, mesh, strategy="hier", optimizer=opt)
    pshapes = jax.eval_shape(lambda k: registry.init(k, cfg),
                             jax.random.key(0))
    batch = jax.eval_shape(lambda: reduced_batch(cfg))
    lowered = step.lower(pshapes, jax.eval_shape(opt.init, pshapes), batch)
    names = _op_names(lowered.compile().as_text())
    layers = LAYERS + ("loss",) + MIXER[family]
    assert any(n.startswith("jit(train_step)/") for n in names)
    assert not _missing(names, layers + ("optimizer", "optimizer/clip"))
    assert not _missing(names, layers, backward=True)
    # one device: the gradient sharding constraint compiles away, so its
    # scope is read from the lowered module's locations
    assert "grad_sync" in lowered.as_text(debug_info=True)


@pytest.mark.parametrize("family", sorted(ARCH))
def test_decode_step_scopes(family, mesh):
    cfg = reduced(ARCHS[ARCH[family]])
    step, *_ = make_serve_step(cfg, mesh)
    pshapes = jax.eval_shape(lambda k: registry.init(k, cfg),
                             jax.random.key(0))
    batch = jax.eval_shape(lambda: reduced_batch(cfg, seq=1))
    extras = {k: v for k, v in batch.items() if k not in ("tokens", "labels")}
    cache = decode_cache_shapes(cfg, 2, 32, extras_shapes=extras or None)
    lowered = step.lower(pshapes, cache, jax.ShapeDtypeStruct((), "int32"),
                         batch["tokens"])
    names = _op_names(lowered.compile().as_text())
    assert not _missing(names, LAYERS + MIXER[family])
    assert any(n.startswith("jit(decode_step)/") for n in names)


def test_sync_grads_scope(mesh):
    """``hier_sync``'s shard_map sync runs under ``grad_sync``."""
    from repro.core.hier_sync import make_sync_grad_fn
    cfg = reduced(ARCHS["olmo-1b"])
    fn = make_sync_grad_fn(lambda p, b: registry.loss_fn(p, cfg, b),
                           jax.sharding.Mesh(mesh.devices[:, 0], ("data",)),
                           "hier")
    pshapes = jax.eval_shape(lambda k: registry.init(k, cfg),
                             jax.random.key(0))
    lowered = jax.jit(fn).lower(pshapes,
                                jax.eval_shape(lambda: reduced_batch(cfg)))
    assert "/grad_sync/" in lowered.as_text(debug_info=True)
