"""Benchmark harness: one module per paper figure/table.

    PYTHONPATH=src python -m benchmarks.run            # all benchmarks
    PYTHONPATH=src python -m benchmarks.run fig7 fig8  # subset
    PYTHONPATH=src python -m benchmarks.run --quick    # CI smoke subset

``--quick`` runs the event-path benchmarks at reduced scale (modules whose
``run`` accepts ``quick=True``) — a smoke check that every registered
module still imports, runs, and emits rows, cheap enough for CI.

Prints a ``name,us_per_call,derived`` CSV line per benchmark (us_per_call is
the harness wall time for that benchmark; `derived` is its headline
result). Detailed rows go to experiments/bench/<name>.json.
"""
from __future__ import annotations

import inspect
import sys
import time

from benchmarks.common import emit_json
from benchmarks import (async_staleness, backend_arbitrage, comm_breakdown,
                        comm_scaling, comm_strategies, config_sensitivity,
                        dynamic_batching, hetero_fleet, multi_job,
                        nas_adaptation, online_learning, optimizer_compare,
                        overlap_pipeline, scenarios, serving_contention,
                        serving_slo, shard_ablation, straggler_tail,
                        workflow_hpo)

BENCHES = {
    "fig1_2_8_comm_scaling": comm_scaling,
    "fig3_config_sensitivity": config_sensitivity,
    "fig4_optimizer_compare": optimizer_compare,
    "fig7_comm_breakdown": comm_breakdown,
    "comm_strategies": comm_strategies,
    "overlap_pipeline": overlap_pipeline,
    "fig9_10_scenarios": scenarios,
    "fig11a_12_dynamic_batching": dynamic_batching,
    "fig11b_online_learning": online_learning,
    "fig13_nas": nas_adaptation,
    "footnote4_shard_ablation": shard_ablation,
    "serving_slo_batching": serving_slo,
    "serving_contention": serving_contention,
    "event_straggler_tail": straggler_tail,
    "event_async_staleness": async_staleness,
    "event_hetero_fleet": hetero_fleet,
    "event_multi_job": multi_job,
    "workflow_hpo": workflow_hpo,
    "backend_arbitrage": backend_arbitrage,
}

# the CI smoke set: the event-path benchmarks (cheap, no BO search inside)
# plus one analytic module, all at reduced scale where supported;
# workflow_hpo runs the orchestrator end to end (successive halving vs
# uniform HPO under one deadline+budget) with reduced rung samples, and
# backend_arbitrage asserts the serverless/gpu_vm flip, the in-budget
# HPO-on-serverless + finetune-on-gpu_vm split, and the hazard-aware
# checkpoint-cadence win over every constant cadence
QUICK = ["fig7_comm_breakdown", "comm_strategies", "overlap_pipeline",
         "event_straggler_tail", "event_async_staleness",
         "event_hetero_fleet", "event_multi_job", "serving_contention",
         "workflow_hpo", "backend_arbitrage"]


def _run_mod(mod, quick: bool):
    if quick and "quick" in inspect.signature(mod.run).parameters:
        return mod.run(quick=True)
    return mod.run()


def main() -> None:
    args = sys.argv[1:]
    quick = "--quick" in args
    which = [a for a in args if not a.startswith("--")]
    which = which or (QUICK if quick else list(BENCHES))
    print("name,us_per_call,derived")
    for name in which:
        mod = BENCHES[[k for k in BENCHES if name in k][0]] \
            if name not in BENCHES else BENCHES[name]
        t0 = time.perf_counter()
        rows = _run_mod(mod, quick)
        us = (time.perf_counter() - t0) * 1e6
        derived = mod.summarize(rows) if hasattr(mod, "summarize") else ""
        print(f"{name},{us:.0f},\"{derived}\"", flush=True)
        emit_json(name, rows)


if __name__ == "__main__":
    main()
