"""Training driver: the worker's train step, driven as ``launch/train.py``
drives it.

Set-up builds one compiled step with its state: ``make_train_step`` on a
(chips, 1) data mesh, parameters from ``registry.init`` jitted into their
step layout from the weight seed, AdamW's state beside them. It then runs
the first three steps through the window's own call and feed, and reads
what the correctness check compares: each step's loss, the norm of each
leaf's first gradient as the optimizer got it (Adam's first moment after
one step over 1 - b1), and the norm of each leaf's change over the three
steps, read before step 4 replaces the parameters. The window then runs
the same object on. Each step draws a batch from ``TokenDataset``, puts it
on the mesh with the step's batch shardings, dispatches, and reads the
loss back.
"""
from __future__ import annotations

import gc
import math
import time
from typing import Dict, List

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation
from jax.sharding import Mesh

from bench import correct, harness, program

FIRST_STEPS = 3


@jax.jit
def _leaf_norms(tree):
    return jax.tree.map(
        lambda x: jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32)))), tree)


@jax.jit
def _diff_norm(a, b):
    d = a.astype(jnp.float32) - b.astype(jnp.float32)
    return jnp.sqrt(jnp.sum(d * d))


def optimizer(cls, hp: Dict):
    """The program's AdamW from the configuration's ``optimizer`` group;
    ``warmup_steps`` becomes its schedule, a linear rise from 0 to the peak
    rate (step t of the optimizer runs at ``lr * min(1, t / warmup)``)."""
    hp = dict(hp)
    warmup = float(hp.pop("warmup_steps", 0))
    if warmup:
        hp["schedule"] = lambda t: jnp.minimum(
            1.0, t.astype(jnp.float32) / warmup)
    return cls(**hp)


def named_leaves(tree) -> Dict[str, object]:
    flat, _ = jax.tree_util.tree_flatten_with_path(tree)
    return {program.leaf_name(p): x for p, x in flat}


class Run:
    def __init__(self, cell, devices):
        t = cell.traffic
        self.cell = cell
        self.devices = list(devices)
        self.chips = len(self.devices)
        if int(t["data_parallel"]) != self.chips:
            raise ValueError(f"traffic asks for {t['data_parallel']} data "
                             f"chips, the cell runs on {self.chips}")
        self.batch = int(t["batch_per_chip"]) * self.chips
        self.seq = int(t["seq_len"])
        self.stats: Dict = {"attempted": 0, "failed": 0}
        self.first: Dict = {}

    # -- set-up ---------------------------------------------------------
    def setup(self):
        from repro.data import DataConfig, ShardedLoader, TokenDataset
        from repro.launch.steps import make_train_step
        from repro.models import registry
        from repro.optim import AdamW

        cfg = program.model_config(self.cell.config)
        seeds = self.cell.seeds()
        self.cfg = cfg
        self.mesh = Mesh(np.array(self.devices).reshape(self.chips, 1),
                         ("data", "model"))
        self.opt = optimizer(AdamW, self.cell.config["optimizer"])
        self.step_fn, pshard, oshard, self.bshard = make_train_step(
            cfg, self.mesh, strategy=self.cell.traffic["strategy"],
            optimizer=self.opt)
        self.params = jax.jit(lambda k: registry.init(k, cfg),
                              out_shardings=pshard)(
            jax.random.key(seeds["weights"]))
        self.opt_state = jax.jit(self.opt.init,
                                 out_shardings=oshard)(self.params)
        self.loader = ShardedLoader(TokenDataset(DataConfig(
            vocab_size=cfg.vocab_size, seq_len=self.seq,
            seed=seeds["data"])))

        p0 = {k: np.asarray(jax.device_get(v))
              for k, v in named_leaves(self.params).items()}
        batches, losses = [], []
        for i in range(FIRST_STEPS):
            batch, loss = self.one_step()
            batches.append(batch["tokens"])
            losses.append(loss)
            if i == 0:
                mu = jax.device_get(_leaf_norms(self.opt_state.mu))
                grad = {k: float(v) / (1.0 - self.opt.b1)
                        for k, v in named_leaves(mu).items()}
        p3 = named_leaves(self.params)
        update = {k: float(_diff_norm(p3[k], jax.device_put(p0[k],
                                                            p3[k].sharding)))
                  for k in p0}
        del p0, p3
        self.first = {"batches": batches, "losses": losses,
                      "grad_norms": grad, "update_norms": update}

    def one_step(self):
        """One step through the window's feed: batch, put, dispatch, read."""
        with TraceAnnotation("bench.next_batch"):
            b = self.loader.next_batch(self.batch)
            b = {"tokens": b["tokens"], "labels": b["labels"]}
        with TraceAnnotation("bench.batch"):
            on_mesh = jax.device_put(b, self.bshard(b))
        with TraceAnnotation("bench.dispatch"):
            self.params, self.opt_state, loss = self.step_fn(
                self.params, self.opt_state, on_mesh)
        with TraceAnnotation("bench.loss_read"):
            loss = float(loss)
        return b, loss

    # -- measurement ----------------------------------------------------
    def window(self, seconds: float):
        steps = failed = 0
        t0 = time.perf_counter()
        while True:
            _, loss = self.one_step()
            steps += 1
            failed += not math.isfinite(loss)
            if time.perf_counter() - t0 >= seconds:
                break
        elapsed = time.perf_counter() - t0
        self.stats.update(
            attempted=steps, failed=failed, steps=steps, window_s=elapsed,
            tokens=steps * self.batch * self.seq, chips=self.chips,
            seq_len=self.seq, batch=self.batch)

    def trace_segment(self):
        n = int(self.cell.traffic["trace_steps"])
        for _ in range(n):
            self.one_step()
        self.stats["trace_steps"] = n

    def free(self):
        self.params = self.opt_state = self.step_fn = None
        gc.collect()

    # -- correctness ----------------------------------------------------
    def check(self) -> List["harness.Check"]:
        ref = harness.load_module("reference", self.cell.config["family"])
        want = ref.train(self.cell.config, self.cell.seeds()["weights"],
                         self.first["batches"], self.devices)
        return correct.train_checks(self.first, want, self.cell.limits)
