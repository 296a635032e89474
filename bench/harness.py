"""Benchmark core: runs one cell once and prints its result line.

Everything that belongs to one configuration, traffic mix or metric is a
file of its own, found by the name ``BENCHMARK.json`` gives it:

  bench/configs/<config>.json     sizes as run, beside the published ones
  bench/traffic/<traffic>.json    parameters; ``driver`` names the generator
  bench/drivers/<driver>.py       the general generator for one kind of work
  bench/metrics/<metric>.py       ``read(r)`` -> number, or None if absent
  bench/counts/<family>.py        operation and byte counts from shapes
  bench/reference/<family>.py     the plain float32 reference
  bench/limits/<workload>.json    the limit of each number compared

This module holds no name of a model, a mix or a metric. A run: check the
platform, let the driver set up (weights from the seed, warm-up, the first
steps the correctness check follows), measure the window, with ``trace``
profile a short segment after it, read peak memory, free the program's
state, let the driver compare with the reference, then read the metrics.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import math
import re
import shutil
import sys
import time
from pathlib import Path
from typing import Any, Dict, List, Optional

import numpy as np

BENCH_DIR = Path(__file__).resolve().parent
ROOT = BENCH_DIR.parent
WORK_DIR = ROOT / ".bench_cache"


class PlatformError(RuntimeError):
    """No accelerator the benchmark can measure."""


@dataclasses.dataclass
class Check:
    """One number compared with the reference, beside its limit."""
    name: str
    value: float
    limit: float

    @property
    def ok(self) -> bool:
        return math.isfinite(self.value) and self.value <= self.limit


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: Dict[str, Any]
    traffic: Dict[str, Any]
    limits: Dict[str, Any]
    seed: int
    seconds: float
    trace: bool

    def seeds(self) -> Dict[str, int]:
        """Independent 31-bit seeds for weights, data, traffic and the
        correctness sample, all from ``--seed`` (any size)."""
        st = np.random.SeedSequence(self.seed).generate_state(4, np.uint32)
        return {k: int(v) & 0x7FFFFFFF
                for k, v in zip(("weights", "data", "traffic", "sample"), st)}


@dataclasses.dataclass
class Reading:
    """What a metric reader sees."""
    cell: Cell
    window: Dict[str, Any]            # the driver's window statistics
    trace: Optional[Any]              # trace.Summary, traced runs only
    peaks: Dict[str, float]
    setup_s: float
    memory_peak_bytes: int

    def counts(self):
        return load_module("counts", self.cell.config["family"])


def load_json(path: Path) -> Dict[str, Any]:
    with open(path) as f:
        return json.load(f)


def load_module(kind: str, name: str):
    path = BENCH_DIR / kind / f"{name}.py"
    mod_name = "bench_" + kind + "_" + re.sub(r"\W", "_", name)
    if mod_name in sys.modules:
        return sys.modules[mod_name]
    spec = importlib.util.spec_from_file_location(mod_name, path)
    if spec is None or not path.exists():
        raise FileNotFoundError(path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[mod_name] = mod
    spec.loader.exec_module(mod)
    return mod


def load_cell(workload: str, *, seed: int, seconds: float, trace: bool,
              spec: Optional[Dict[str, Any]] = None) -> Cell:
    spec = spec or load_json(ROOT / "BENCHMARK.json")
    cells = {w["name"]: w for w in spec["workloads"]}
    if workload not in cells:
        raise KeyError(f"no workload {workload!r} in BENCHMARK.json")
    w = cells[workload]
    conf = {c["name"]: c for c in spec["configs"]}[w["config"]]
    return Cell(name=workload, chips=int(w["chips"]),
                config=load_json(ROOT / conf["file"]),
                traffic=load_json(BENCH_DIR / "traffic"
                                  / f"{w['traffic']}.json"),
                limits=load_json(BENCH_DIR / "limits" / f"{workload}.json"),
                seed=seed, seconds=seconds, trace=trace)


def peaks_for(kind: str) -> Dict[str, float]:
    table = load_json(BENCH_DIR / "peaks.json")["devices"]
    if kind not in table:
        raise PlatformError(f"device kind {kind!r} is not in bench/peaks.json")
    return table[kind]


def check_platform(chips: int):
    """-> (devices the cell uses, their peaks). Refuses anything but TPU."""
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise PlatformError(f"platform {devs[0].platform!r} is not tpu")
    if len(devs) < chips:
        raise PlatformError(f"{len(devs)} chips, the cell asks for {chips}")
    return devs[:chips], peaks_for(devs[0].device_kind)


def log(msg: str):
    print(f"bench: {msg}", file=sys.stderr, flush=True)


def memory_peak(devices) -> int:
    peaks = []
    for d in devices:
        stats = d.memory_stats() or {}
        peaks.append(int(stats.get("peak_bytes_in_use", 0)))
    return max(peaks) if peaks else 0


def metric_names(spec: Dict[str, Any], cell: str, trace: bool) -> List[Dict]:
    group = spec["per_layer"] if trace else spec["end_to_end"]
    return [m for m in group if cell in m.get("workloads", [cell])]


def traced_segment(run, cell: Cell):
    """Profile the driver's trace segment and reduce it; the trace files
    are deleted once read."""
    import jax
    from bench import trace as tr
    out = WORK_DIR / "trace" / cell.name
    shutil.rmtree(out, ignore_errors=True)
    jax.profiler.start_trace(str(out))
    try:
        run.trace_segment()
    finally:
        jax.profiler.stop_trace()
    try:
        return tr.reduce(tr.load(tr.find_xplane(str(out))))
    finally:
        shutil.rmtree(out, ignore_errors=True)


def execute(cell: Cell, spec: Dict[str, Any], *, process_t0: float,
            devices, peaks: Dict[str, float]) -> Dict[str, Any]:
    """Run the cell on ``devices`` and return the result line's object."""
    driver = load_module("drivers", cell.traffic["driver"])
    run = driver.Run(cell, devices)
    run.setup()
    setup_s = time.perf_counter() - process_t0
    log(f"set-up {setup_s:.3f} s")
    run.window(cell.seconds)
    summary = traced_segment(run, cell) if cell.trace else None
    mem = memory_peak(devices)
    run.free()
    t0 = time.perf_counter()
    checks: List[Check] = run.check()
    log(f"reference and comparison {time.perf_counter() - t0:.3f} s")

    reading = Reading(cell=cell, window=run.stats, trace=summary, peaks=peaks,
                      setup_s=setup_s, memory_peak_bytes=mem)
    metrics = {}
    for m in metric_names(spec, cell.name, cell.trace):
        value = load_module("metrics", m["name"]).read(reading)
        if value is not None:
            metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}

    d0 = devices[0]
    device = {"platform": d0.platform, "kind": d0.device_kind,
              "count": len(devices), "memory_peak_bytes": mem}
    out: Dict[str, Any] = {
        "correct": bool(checks) and all(c.ok for c in checks)
        and run.stats["failed"] == 0,
        "attempted": int(run.stats["attempted"]),
        "failed": int(run.stats["failed"]),
        "metrics": metrics,
        "device": device,
    }
    if summary is not None:
        device["busy_s"] = summary.busy_s
        device["window_s"] = summary.window_s
        out["breakdown"] = {"device_ops": [list(x) for x in summary.device_ops],
                            "idle_gaps": [list(x) for x in summary.idle_gaps]}
    out["checks"] = {c.name: {"value": c.value, "limit": c.limit}
                     for c in checks}
    return out


def print_result(out: Dict[str, Any]):
    for name, c in out["checks"].items():
        verdict = "ok" if math.isfinite(c["value"]) and c["value"] <= c["limit"] \
            else "FAIL"
        print(f"check {name} {c['value']!r} limit {c['limit']!r} {verdict}",
              file=sys.stderr, flush=True)
    print(json.dumps(out), flush=True)


def main(workload: str, *, seed: int, seconds: float, trace: bool,
         process_t0: float) -> int:
    spec = load_json(ROOT / "BENCHMARK.json")
    cell = load_cell(workload, seed=seed, seconds=seconds, trace=trace,
                     spec=spec)
    from repro.launch.compile_cache import enable_compile_cache
    import jax
    enable_compile_cache()
    # every program of the cell goes into the persistent cache
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    try:
        devices, peaks = check_platform(cell.chips)
    except PlatformError as e:
        log(str(e))
        return 2
    print_result(execute(cell, spec, process_t0=process_t0, devices=devices,
                         peaks=peaks))
    return 0
