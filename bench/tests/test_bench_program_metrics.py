"""The per-layer metrics read from the program's own spans and counters
(``repro.obs``): the traced CPU rehearsal of each cell that lists them
reads them, and the untraced run's metric set stays the end-to-end one.

Several rehearsals share one process here, as they do under pytest: the
two compile readings are the latest build's, so each run reads its own
step, even after a build that never compiled (the ``unchanged`` fault).
"""
from __future__ import annotations

import pytest

from bench import harness
from bench.tests.test_bench_cells import SPEC, check_result, run_with

PROGRAM = ("batch_ms.train", "step_compile_s.train", "step_compiles.train")
CELLS = sorted({c for m in SPEC["per_layer"] if m["name"] in PROGRAM
                for c in m["workloads"]})


def check_program_metrics(out):
    m = {k: v["value"] for k, v in out["metrics"].items()}
    assert m["batch_ms.train"] > 0
    assert m["step_compiles.train"] == 1
    assert m["step_compile_s.train"] > 0


@pytest.mark.parametrize("name", CELLS)
def test_traced_rehearsal_reads_program_metrics(name):
    out = run_with(name, trace=True)
    check_result(name, out, True)
    check_program_metrics(out)
    run_with(name, "unchanged")          # a build that never compiles
    out = run_with(name, trace=True, seed=2 ** 35 + 1)
    assert out["correct"], out["checks"]
    check_program_metrics(out)


@pytest.mark.parametrize("name", CELLS)
def test_untraced_rehearsal_keeps_its_metrics(name):
    out = run_with(name)
    check_result(name, out, False)
    assert set(out["metrics"]) == {m["name"] for m in
                                   harness.metric_names(SPEC, name, False)}
    assert not set(out["metrics"]) & set(PROGRAM)
