"""Quickstart: the three layers of SMLT in one minute.

  1. the REAL training path — hierarchical sync on a model from the zoo;
  2. the SCHEDULER — user-centric deadline goal on the serverless simulator;
  3. the KERNELS — Pallas shard aggregation vs its oracle.

Run:  PYTHONPATH=src python examples/quickstart.py
"""
import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import ARCHS, reduced
from repro.core import EpochPlan, Goal
from repro.kernels import ops, ref
from repro.launch.train import train
from repro.models import registry
from repro.serverless import WORKLOADS


def fresh_scheduler(scheme="hier", seed=0, max_workers=200):
    from repro.core import ConfigSpace, TaskScheduler
    from repro.serverless import ObjectStore, ParamStore, ServerlessPlatform
    plat = ServerlessPlatform(seed=seed)
    sched = TaskScheduler(plat, ObjectStore(), ParamStore(), scheme=scheme,
                          space=ConfigSpace(max_workers=max_workers),
                          seed=seed)
    return (sched, plat)


# 1. real training: reduced olmo-1b, hierarchical (RS+AG) gradient sync
cfg = reduced(ARCHS["olmo-1b"])
print(f"[1/3] training reduced {cfg.arch_id} "
      f"({registry.param_count(cfg)/1e6:.1f}M params)")
_, losses, _ = train(cfg, steps=40, batch=8, seq=64, strategy="hier",
                  lr=1e-3, log_every=20)
assert losses[-1] < losses[0]

# 2. scheduler: minimize cost under a 1-hour deadline (paper Scenario 1)
print("[2/3] SMLT scheduler, Scenario 1 (min cost s.t. T <= 1h)")
sched, *_ = fresh_scheduler("hier")
res = sched.run([EpochPlan(1024, WORKLOADS["bert-small"], samples=30_000)
                 for _ in range(3)],
                Goal("min_cost_deadline", deadline_s=3600.0),
                stop_at_deadline=True)
cfgs = {(c.workers, c.memory_mb) for c in res.config_history}
print(f"      deployed {cfgs}; wall {res.wall_s:.0f}s <= 3600s; "
      f"cost ${res.total_cost:.2f} (profiling ${res.profile_usd:.2f})")

# 3. Pallas kernel == oracle
print("[3/3] Pallas hier_agg kernel vs jnp oracle")
shards = jnp.array(np.random.RandomState(0).randn(8, 4096), jnp.float32)
np.testing.assert_allclose(ops.aggregate_shards(shards),
                           ref.ref_aggregate(shards), rtol=1e-6, atol=1e-6)
print("      allclose OK")
print("quickstart done.")
