"""Readings behind the limits in ``bench/limits``: the program on a dozen
seeds or more, the control and the planted faults on three or more, at the
cell's own size and in one process. The benchmark's own runs never run it.

    python3 bench/calibrate.py --workload train.olmo-1b --seeds 12 \
        --control-seeds 3 --out chiprun_out/calibrate.train.olmo-1b.json

The control is the reference put in the program's place, computed one
precision below the configuration's bfloat16 products: float8 e4m3
operands for every matrix product. Training cells also read the planted faults of the
reference: ``half_batch`` (the loss and gradient over half of each batch)
and, across chips, ``local_grad`` (the gradient of the first chip's rows
alone: the exchange between chips left out). A state left unchanged reads
1 on ``grad_gap`` and ``update_gap`` by their definition and needs no run.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)


def summarize(readings):
    out = {}
    for kind, rows in readings.items():
        if not rows:
            continue
        keys = rows[0].keys()
        agg = max if kind == "program" else min
        out[kind] = {k: agg(r[k] for r in rows) for k in keys}
    return out


def calibrate_train(cells, devices, *, control_seeds: int):
    """cells: one Cell per seed, all of one training workload."""
    from bench import correct, harness
    readings = {"program": [], "control": [], "half_batch": [],
                "local_grad": []}
    for i, cell in enumerate(cells):
        ref_mod = harness.load_module("reference", cell.config["family"])
        run = harness.load_module("drivers", cell.traffic["driver"]).Run(
            cell, devices)
        run.setup()
        prog = run.first
        run.free()
        wseed, batches = cell.seeds()["weights"], prog["batches"]
        want = ref_mod.train(cell.config, wseed, batches, devices)
        row = {"program": correct.train_numbers(prog, want)}
        if i < control_seeds:
            ctl = ref_mod.train(cell.config, wseed, batches, devices,
                                mode="fp8")
            row["control"] = correct.train_numbers(ctl, want)
            faults = ["half_batch"] + (["local_grad"] if len(devices) > 1
                                       else [])
            for fault in faults:
                got = ref_mod.train(cell.config, wseed, batches, devices,
                                    fault=fault)
                row[fault] = correct.train_numbers(got, want)
        for k, v in row.items():
            readings[k].append(v)
        print(json.dumps({"seed": cell.seed, **row}), file=sys.stderr,
              flush=True)
    return readings


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, default=12)
    ap.add_argument("--control-seeds", type=int, default=3)
    ap.add_argument("--first-seed", type=int, default=3_000_000_001)
    ap.add_argument("--out", required=True)
    args = ap.parse_args(argv)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(
        ROOT, ".bench_cache", "jax")
    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
    import jax
    from bench import harness
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    spec = harness.load_json(harness.ROOT / "BENCHMARK.json")
    chips = {w["name"]: w["chips"] for w in spec["workloads"]}[args.workload]
    devices, _ = harness.check_platform(chips)
    cells = [harness.load_cell(args.workload, seed=args.first_seed + i,
                               seconds=0, trace=False, spec=spec)
             for i in range(args.seeds)]
    t0 = time.perf_counter()
    readings = calibrate_train(cells, devices,
                               control_seeds=args.control_seeds)
    out = {"workload": args.workload, "seconds": time.perf_counter() - t0,
           "device": devices[0].device_kind, "readings": readings,
           "summary": summarize(readings)}
    os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
    with open(args.out, "w") as f:
        json.dump(out, f, indent=1)
    print(json.dumps(out["summary"]))
    return 0


if __name__ == "__main__":
    sys.exit(main())
