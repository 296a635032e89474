"""Rehearsal of every cell on the CPU at ``configs.reduced()`` sizes, the
entry point's refusal without a TPU, and the faults that must turn
``correct`` false.

Each run goes through ``harness.execute``, which is the benchmark's run
without its look for a chip: the cell's own driver, metric readers, trace
reduction, reference and limits. The faults are planted underneath the
timed path: a train step that returns its state unchanged, and one that
leaves half of the batch out. The control (the reference in float8, in the
program's place) must fail at least one number too.
"""
from __future__ import annotations

import copy
import os
import shutil
import subprocess
import sys
import time

import pytest

from bench import harness, program

PEAKS = {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9}
SPEC = harness.load_json(harness.ROOT / "BENCHMARK.json")
CELLS = [w["name"] for w in SPEC["workloads"]]
ONE_CHIP = [w["name"] for w in SPEC["workloads"] if w["chips"] == 1]
TRAIN = [w["name"] for w in SPEC["workloads"]
         if harness.load_json(harness.BENCH_DIR / "traffic"
                              / f"{w['traffic']}.json")["driver"] == "train"]


def small_cell(name: str, *, seed: int = 2 ** 33 + 7, trace: bool = False,
               seconds: float = 1.0) -> harness.Cell:
    """The cell with its model at ``configs.reduced()`` sizes and short
    sequences; everything else as committed."""
    from repro.configs import reduced
    cell = harness.load_cell(name, seed=seed, seconds=seconds, trace=trace,
                             spec=SPEC)
    small = reduced(program.model_config(cell.config))
    cell.config = copy.deepcopy(cell.config)
    cell.config["model"].update(
        n_layers=small.n_layers, d_model=small.d_model, n_heads=small.n_heads,
        n_kv_heads=small.n_kv_heads, head_dim=small.head_dim, d_ff=small.d_ff,
        vocab_size=small.vocab_size, dtype="float32", remat=False)
    cell.traffic = dict(cell.traffic, seq_len=64, trace_steps=2)
    return cell


def run_cell(cell: harness.Cell):
    import jax
    return harness.execute(cell, SPEC, process_t0=time.perf_counter(),
                           devices=jax.devices()[:cell.chips], peaks=PEAKS)


def plant(fault: str):
    """Patch the program underneath the timed path; returns an undo."""
    import jax

    import repro.launch.steps as steps
    from repro.models import registry

    orig = steps.make_train_step

    def make(cfg, mesh, **kw):
        step, pshard, oshard, bshard = orig(cfg, mesh, **kw)
        if fault == "unchanged":
            loss = jax.jit(lambda p, b: registry.loss_fn(p, cfg, b))
            return (lambda p, o, b: (p, o, loss(p, b))), pshard, oshard, bshard
        if fault == "half_batch":
            def half(p, o, b):
                n = b["tokens"].shape[0] // 2
                return step(p, o, {k: v[:n] for k, v in b.items()})
            return half, pshard, oshard, bshard
        raise ValueError(fault)

    steps.make_train_step = make
    return lambda: setattr(steps, "make_train_step", orig)


def run_with(name: str, fault=None, **kw):
    undo = plant(fault) if fault else (lambda: None)
    try:
        return run_cell(small_cell(name, **kw))
    finally:
        undo()


def check_result(name: str, out, trace: bool):
    assert list(out) == (["correct", "attempted", "failed", "metrics",
                          "device"] + (["breakdown"] if trace else [])
                         + ["checks"])
    want = {m["name"] for m in harness.metric_names(SPEC, name, trace)}
    assert set(out["metrics"]) <= want
    assert out["attempted"] > 0 and out["failed"] == 0
    if not trace:
        assert "setup_s" in out["metrics"]
        assert len(out["metrics"]) >= 2
    else:
        assert out["device"]["busy_s"] > 0 and out["device"]["window_s"] > 0
        assert len(out["breakdown"]["device_ops"]) <= 10
    assert all("value" in c and "limit" in c for c in out["checks"].values())


@pytest.mark.parametrize("trace", [False, True])
@pytest.mark.parametrize("name", ONE_CHIP)
def test_one_chip_cell_rehearsal(name, trace):
    out = run_with(name, trace=trace)
    check_result(name, out, trace)
    assert out["correct"], out["checks"]


@pytest.mark.parametrize("name", [c for c in TRAIN if c in ONE_CHIP])
@pytest.mark.parametrize("fault", ["unchanged", "half_batch"])
def test_train_fault_is_not_correct(name, fault):
    out = run_with(name, fault)
    assert not out["correct"], out["checks"]


@pytest.mark.parametrize("name", [c for c in TRAIN if c in ONE_CHIP])
def test_train_control_is_not_correct(name):
    import jax
    from bench import correct
    cell = small_cell(name)
    run = harness.load_module("drivers", "train").Run(cell, jax.devices()[:1])
    run.setup()
    batches = run.first["batches"]
    run.free()
    ref = harness.load_module("reference", cell.config["family"])
    wseed = cell.seeds()["weights"]
    want = ref.train(cell.config, wseed, batches, jax.devices()[:1])
    ctl = ref.train(cell.config, wseed, batches, jax.devices()[:1],
                    mode="fp8")
    checks = correct.train_checks(ctl, want, cell.limits)
    assert not all(c.ok for c in checks), checks


def test_reference_weights_are_the_programs():
    """The reference makes the program's weights from the seed by its own
    recipe, without taking them from the program."""
    import jax
    import numpy as np
    from repro.models import registry
    cell = small_cell(TRAIN[0])
    cell.config["model"]["dtype"] = "bfloat16"
    cfg = program.model_config(cell.config)
    key = jax.random.key(cell.seeds()["weights"])
    prog = registry.init(key, cfg)
    ref = harness.load_module("reference", cell.config["family"])
    D = ref.dims(cell.config)
    emb = ref.embed_weights(key, D)
    np.testing.assert_array_equal(np.asarray(prog["embed"]["tok"], np.float32),
                                  np.asarray(emb["tok"]))
    for i in range(D.layers):
        w = ref.layer_weights(key, i, D)
        for k, v in w.items():
            a, b = k.split("/")
            np.testing.assert_array_equal(
                np.asarray(prog["blocks"][a][b][i], np.float32),
                np.asarray(v))


def test_entry_refuses_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    r = subprocess.run([sys.executable, "bench/run.py", "--workload", CELLS[0],
                        "--seed", str(2 ** 40), "--seconds", "1", "--trace",
                        "0"], cwd=harness.ROOT, env=env, capture_output=True,
                       text=True, timeout=300)
    assert r.returncode != 0
    assert r.stdout == ""
    assert "not tpu" in r.stderr


def test_entry_fails_with_only_the_benchmark(tmp_path):
    shutil.copy(harness.ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(harness.BENCH_DIR, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    r = subprocess.run([sys.executable, "bench/run.py", "--workload", CELLS[0],
                        "--seed", "3", "--seconds", "1", "--trace", "0"],
                       cwd=tmp_path, env=dict(os.environ, JAX_PLATFORMS="cpu",
                                              PYTHONPATH=""),
                       capture_output=True, text=True, timeout=300)
    assert r.returncode != 0
    assert r.stdout == ""

