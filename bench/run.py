"""Run one cell of the chip benchmark once.

    python3 bench/run.py --workload train.olmo-1b --seed 7 --seconds 30 --trace 0

The cell, its configuration file and its traffic file are found by name in
``BENCHMARK.json`` at the root of the checkout. The last line of standard
output is one JSON object (``correct``, ``attempted``, ``failed``,
``metrics``, ``device``, with ``--trace 1`` also ``breakdown``, and last
``checks``: each number compared, beside its limit). Without a TPU, with a
device kind missing from ``bench/peaks.json`` or with fewer chips than the
cell asks for, it exits non-zero and prints no result.
"""
from __future__ import annotations

import time

PROCESS_T0 = time.perf_counter()

import argparse  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)
# JAX's persistent compilation cache lives at a fixed path inside the
# checkout (the path is part of the cache key); the program's own
# ``enable_compile_cache`` takes whatever this variable names.
CACHE_DIR = os.path.join(ROOT, ".bench_cache", "jax")


def parse_args(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def main(argv=None) -> int:
    args = parse_args(argv)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = CACHE_DIR
    for p in (ROOT, os.path.join(ROOT, "src")):
        if p not in sys.path:
            sys.path.insert(0, p)
    from bench import harness
    return harness.main(args.workload, seed=args.seed, seconds=args.seconds,
                        trace=bool(args.trace), process_t0=PROCESS_T0)


if __name__ == "__main__":
    sys.exit(main())
