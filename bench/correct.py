"""The comparisons that decide ``correct``: the numbers a run reports
beside their limits. The limits live in ``bench/limits/<workload>.json``,
each with the readings it was set from (``PERF.md`` gives them too).

Training (the program's first three steps against the reference's):

  loss_gap     the largest |loss - reference loss| over the three steps
  grad_gap     over leaves, the gap between the norms of the program's and
               the reference's first (clipped) gradient, over the larger of
               that leaf's reference norm and the median leaf's
  update_gap   the same of each leaf's change over the three steps, over
               leaves whose reference gradient is at least a thousandth of
               the median leaf's (a gradient nought to rounding moves its
               leaf by round-off alone)
"""
from __future__ import annotations

from typing import Dict, List

import numpy as np

from bench.harness import Check

SMALL_GRAD = 1e-3


def leaf_gap(prog: Dict[str, float], ref: Dict[str, float],
             leaves=None) -> float:
    leaves = sorted(ref) if leaves is None else leaves
    if set(prog) != set(ref):
        return float("inf")
    med = float(np.median([ref[k] for k in ref]))
    return max(abs(prog[k] - ref[k]) / max(ref[k], med) for k in leaves)


def train_numbers(prog: Dict, ref: Dict) -> Dict[str, float]:
    grads = ref["grad_norms"]
    med = float(np.median(list(grads.values())))
    moving = [k for k in sorted(grads) if grads[k] >= SMALL_GRAD * med]
    return {
        "loss_gap": max(abs(a - b) for a, b in zip(prog["losses"],
                                                    ref["losses"])),
        "grad_gap": leaf_gap(prog["grad_norms"], grads),
        "update_gap": leaf_gap(prog["update_norms"], ref["update_norms"],
                               moving),
    }


def train_checks(prog: Dict, ref: Dict, limits: Dict) -> List[Check]:
    return [Check(k, float(v), float(limits[k]["limit"]))
            for k, v in train_numbers(prog, ref).items()]
