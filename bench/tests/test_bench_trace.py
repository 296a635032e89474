"""The trace reduction (bench/trace.py) on hand-made events and on a small
trace recorded on the CPU (bench/tests/data/cpu_trace.xplane.pb).

Regenerate the recorded trace with
``JAX_PLATFORMS=cpu python3 -m bench.tests.test_bench_trace``: three steps of
a jitted matrix product, each in ``bench.dispatch`` and ``bench.loss_read``
spans, after a 30 ms ``bench.next_batch`` span in which the host sleeps.
"""
from __future__ import annotations

import os
import shutil
import tempfile
import time

import pytest

from bench import trace

DATA = os.path.join(os.path.dirname(__file__), "data", "cpu_trace.xplane.pb")
SLEEP_S = 0.03


def test_interval_arithmetic():
    assert trace.union([(5, 7), (0, 2), (1, 3), (7, 8)]) == [(0, 3), (5, 8)]
    assert trace.subtract([(0, 10)], [(2, 3), (2.5, 4), (9, 12)]) == \
        [(0, 2), (4, 9)]
    assert trace.clip([(0, 5), (6, 9)], 1, 7) == [(1, 5), (6, 7)]
    assert trace.length([(0, 2), (5, 8)]) == 5


def test_reduce_hand_made_events():
    ms = 1e6  # ns per ms
    tr = trace.Trace(
        devices={
            "/device:TPU:0": [("fusion.1", 0, 4 * ms),
                              ("all-reduce.2", 3 * ms, 6 * ms),
                              ("fusion.3", 8 * ms, 9 * ms)],
            "/device:TPU:1": [("fusion.1", 0, 2 * ms)],
        },
        spans=[("bench.dispatch", 0, 7 * ms),
               ("bench.loss_read", 7 * ms, 10 * ms)])
    s = trace.reduce(tr)
    assert s.window_s == pytest.approx(0.010)
    assert s.busy_s == pytest.approx((0.007 + 0.002) / 2)
    assert s.collective_s == pytest.approx(0.003)
    assert s.exposed_collective_s == pytest.approx(0.002)
    assert s.device_ops[0] == ("fusion.1", pytest.approx(0.004))
    assert dict(s.idle_gaps) == {"bench.loss_read": pytest.approx(0.002),
                                 "bench.dispatch": pytest.approx(0.001)}
    assert s.n_devices == 2


def test_reduce_refuses_an_empty_trace():
    with pytest.raises(ValueError):
        trace.reduce(trace.Trace(devices={}, spans=[("bench.x", 0, 1)]))
    with pytest.raises(ValueError):
        trace.reduce(trace.Trace(devices={"d": [("op", 0, 1)]}, spans=[]))


def test_recorded_cpu_trace():
    tr = trace.load(DATA)
    names = {n for n, _, _ in tr.spans}
    assert names == {"bench.next_batch", "bench.dispatch", "bench.loss_read"}
    assert sum(n == "bench.next_batch" for n, _, _ in tr.spans) == 3
    s = trace.reduce(tr)
    assert 0 < s.busy_s < s.window_s
    assert s.n_devices == 1
    gaps = dict(s.idle_gaps)
    # the host slept in bench.next_batch three times while the device idled
    assert gaps["bench.next_batch"] >= 3 * SLEEP_S * 0.9
    assert s.window_s >= 3 * SLEEP_S
    assert any(n.startswith("dot") for n, _ in s.device_ops)
    assert s.collective_s == 0


def record(path: str):
    import jax
    import jax.numpy as jnp
    f = jax.jit(lambda x: jnp.tanh(x @ x).sum())
    x = jnp.ones((256, 256))
    f(x).block_until_ready()
    tmp = tempfile.mkdtemp()
    try:
        jax.profiler.start_trace(tmp)
        for _ in range(3):
            with jax.profiler.TraceAnnotation("bench.next_batch"):
                time.sleep(SLEEP_S)
            with jax.profiler.TraceAnnotation("bench.dispatch"):
                y = f(x)
            with jax.profiler.TraceAnnotation("bench.loss_read"):
                float(y)
        jax.profiler.stop_trace()
        shutil.copy(trace.find_xplane(tmp), path)
    finally:
        shutil.rmtree(tmp, ignore_errors=True)


if __name__ == "__main__":
    record(DATA)
