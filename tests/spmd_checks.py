"""Subprocess payload for multi-device SPMD tests.

Run as: python tests/spmd_checks.py <check-name>
(sets XLA_FLAGS for 8 host devices BEFORE importing jax — kept out of the
pytest process so smoke tests/benches still see 1 device).
"""
import os
import sys

os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
from jax.sharding import Mesh  # noqa: E402

from repro.core import make_sync_grad_fn  # noqa: E402
from repro.core.elastic import ElasticRunner, make_data_mesh  # noqa: E402
from repro.optim import AdamW  # noqa: E402


def loss_fn(params, batch):
    pred = jnp.tanh(batch["x"] @ params["w1"]) @ params["w2"]
    return jnp.mean((pred - batch["y"]) ** 2)


def make_problem(seed=0):
    rng = np.random.RandomState(seed)
    params = {"w1": jnp.array(rng.randn(6, 16) * 0.3, jnp.float32),
              "w2": jnp.array(rng.randn(16, 3) * 0.3, jnp.float32)}
    batch = {"x": jnp.array(rng.randn(32, 6), jnp.float32),
             "y": jnp.array(rng.randn(32, 3), jnp.float32)}
    return params, batch


def check_sync_equivalence():
    params, batch = make_problem()
    ref_loss, ref_grads = jax.value_and_grad(loss_fn)(params, batch)
    meshes = [Mesh(np.array(jax.devices()).reshape(2, 4), ("pod", "data")),
              Mesh(np.array(jax.devices()), ("data",))]
    for mesh in meshes:
        strategies = ["allreduce", "hier", "ps"]
        if "pod" in mesh.axis_names:
            strategies.append("hier2")
        for strat in strategies:
            f = make_sync_grad_fn(loss_fn, mesh, strat)
            loss, grads = f(params, batch)
            np.testing.assert_allclose(np.asarray(loss), np.asarray(ref_loss),
                                       rtol=1e-5)
            for a, b in zip(jax.tree.leaves(grads), jax.tree.leaves(ref_grads)):
                np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                           rtol=1e-4, atol=1e-5)
    print("OK sync_equivalence")


def check_sync_property():
    """Random pytrees with awkward shapes (incl. not divisible by n) stay
    exactly mean-reduced under the hierarchical strategy."""
    from repro.core.hier_sync import sync_grads
    mesh = Mesh(np.array(jax.devices()), ("data",))
    rng = np.random.RandomState(1)
    for trial in range(5):
        shapes = [tuple(rng.randint(1, 9) for _ in range(rng.randint(1, 4)))
                  for _ in range(4)]
        tree = {f"p{i}": jnp.array(rng.randn(8, *s), jnp.float32)
                for i, s in enumerate(shapes)}  # leading dim = per-device

        def f(tree):
            return sync_grads(tree, "hier", n_data=8)

        specs = jax.tree.map(
            lambda _: jax.sharding.PartitionSpec("data"), tree)
        out = jax.shard_map(f, mesh=mesh, in_specs=(specs,),
                            out_specs=specs, check_vma=False)(tree)
        for k in tree:
            want = np.broadcast_to(np.asarray(tree[k]).mean(0, keepdims=True),
                                   tree[k].shape)
            np.testing.assert_allclose(np.asarray(out[k]), want,
                                       rtol=1e-5, atol=1e-6)
    print("OK sync_property")


def check_elastic():
    """Rescaling the fleet mid-training keeps training exact: loss path on
    (4 workers -> 8 workers) matches a fixed 8-worker run (data parallel sync
    is exact, so fleet size must not change the math)."""
    params, batch = make_problem()
    opt = AdamW(lr=0.05, weight_decay=0.0, grad_clip=0.0)

    def builder(mesh):
        f = make_sync_grad_fn(loss_fn, mesh, "hier")

        def step(params, opt_state, batch):
            loss, grads = f(params, batch)
            params, opt_state = opt.update(grads, opt_state, params)
            return params, opt_state, loss

        return step

    def run(schedule):
        r = ElasticRunner(builder, params, opt.init(params),
                          n_workers=schedule[0])
        losses = []
        for i, n in enumerate(schedule):
            r.rescale(n)
            losses.append(float(r.train_step(batch)))
        return losses

    a = run([4, 4, 8, 8, 2, 8])
    b = run([8] * 6)
    np.testing.assert_allclose(a, b, rtol=1e-5)
    assert a[-1] < a[0], "loss must decrease"
    print("OK elastic")


def check_hier2_q():
    """bf16-compressed cross-pod hop: grads within bf16 tolerance of exact."""
    params, batch = make_problem()
    mesh = Mesh(np.array(jax.devices()).reshape(2, 4), ("pod", "data"))
    ref_loss, ref_grads = jax.value_and_grad(loss_fn)(params, batch)
    f = make_sync_grad_fn(loss_fn, mesh, "hier2_q")
    loss, grads = f(params, batch)
    np.testing.assert_allclose(np.asarray(loss), np.asarray(ref_loss),
                               rtol=1e-5)
    for a, b in zip(jax.tree.leaves(grads), jax.tree.leaves(ref_grads)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-2, atol=1e-3)  # bf16 hop
    print("OK hier2_q")


if __name__ == "__main__":
    {"sync_equivalence": check_sync_equivalence,
     "sync_property": check_sync_property,
     "elastic": check_elastic,
     "hier2_q": check_hier2_q}[sys.argv[1]]()
