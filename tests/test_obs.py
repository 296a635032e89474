"""``repro.obs``: span aggregates and self time, counters, the compile
listener, and host spans on the profiler's clock beside the XLA operations.

The module's state is process-wide, so every test reads the difference
between two snapshots, under names no other code uses.
"""
import glob
import subprocess
import sys
import threading
import time

import jax
import jax.numpy as jnp
import pytest

from repro import obs


def _span(name):
    return obs.snapshot()["spans"].get(name, {"count": 0, "total_s": 0.0,
                                              "self_s": 0.0, "max_s": 0.0})


def test_span_aggregates_and_self_time():
    with obs.span("smlt.test.outer") as outer:
        time.sleep(0.01)
        for _ in range(2):
            with obs.span("smlt.test.inner"):
                time.sleep(0.005)
    o, i = _span("smlt.test.outer"), _span("smlt.test.inner")
    assert o["count"] == 1 and i["count"] == 2
    assert o["total_s"] == pytest.approx(outer.seconds, abs=1e-9)
    assert o["max_s"] == o["total_s"]
    # the outer span's self time is its duration less its children's
    assert o["self_s"] == pytest.approx(o["total_s"] - i["total_s"], abs=1e-8)
    assert 0.01 <= o["self_s"] < o["total_s"]
    assert i["self_s"] == i["total_s"] >= 0.01
    assert i["max_s"] >= 0.005


def test_spans_on_another_thread_are_not_children():
    started, release = threading.Event(), threading.Event()

    def other():
        with obs.span("smlt.test.thread_b"):
            started.set()
            release.wait(5)

    t = threading.Thread(target=other)
    with obs.span("smlt.test.thread_a"):
        t.start()
        assert started.wait(5)
        time.sleep(0.02)
        release.set()
        t.join(5)
    assert not t.is_alive()
    a, b = _span("smlt.test.thread_a"), _span("smlt.test.thread_b")
    assert a["self_s"] == a["total_s"] and b["self_s"] == b["total_s"]
    assert b["total_s"] >= 0.02


def test_spans_and_counters_lose_no_update_across_threads():
    threads, per = 16, 300
    before = obs.snapshot()["counters"].get("test.hits", 0)
    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        def work():
            for _ in range(per):
                with obs.span("smlt.test.stress"):
                    with obs.span("smlt.test.stress_child"):
                        obs.count("test.hits")
        ts = [threading.Thread(target=work) for _ in range(threads)]
        for t in ts:
            t.start()
        for t in ts:
            t.join(60)
        assert not any(t.is_alive() for t in ts)
    finally:
        sys.setswitchinterval(old)
    s, c = _span("smlt.test.stress"), _span("smlt.test.stress_child")
    assert s["count"] == c["count"] == threads * per
    assert obs.snapshot()["counters"]["test.hits"] - before == threads * per
    assert s["self_s"] == pytest.approx(s["total_s"] - c["total_s"], abs=1e-6)


def test_counters():
    obs.count("test.counter")
    obs.count("test.counter", 5)
    assert obs.snapshot()["counters"]["test.counter"] == 6


def _make_fn():
    """A new function object each call, as ``make_train_step`` builds one."""
    def obs_test_fn(x):
        return jnp.sin(x) * 2
    return obs_test_fn


def test_compile_listener_counts_backend_compiles():
    obs.build("obs_test_fn")
    f = jax.jit(_make_fn())

    def compiles():
        return obs.snapshot()["last_build"]["obs_test_fn"]["compiles"]

    f(jnp.ones(3))
    assert compiles() == 1
    f(jnp.ones(3))
    assert compiles() == 1          # same shapes: no compile
    f(jnp.ones(4))
    assert compiles() == 2          # a new shape compiles again
    rec = obs.snapshot()["last_build"]["obs_test_fn"]
    assert rec["compile_s"] > 0 and rec["trace_s"] > 0 and rec["lower_s"] > 0
    assert obs.snapshot()["counters"]["obs_test_fn.builds"] == 1
    # a new build starts its record afresh; the totals keep both
    obs.build("obs_test_fn")
    jax.jit(_make_fn())(jnp.ones(3))
    assert compiles() == 1
    assert obs.snapshot()["compiles"]["obs_test_fn"]["compiles"] == 3


CACHE_SCRIPT = """
import sys
import jax, jax.numpy as jnp
jax.config.update("jax_compilation_cache_dir", sys.argv[1])
jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
from repro import obs

def make():  # the same program as a new function: traced and lowered anew
    def cached_fn(x):
        return jnp.cos(x) + 1
    return cached_fn

out = []
for _ in range(2):
    obs.build("cached_fn")
    jax.jit(make())(jnp.ones(5))
    rec = obs.snapshot()["last_build"]["cached_fn"]
    out += [rec["compiles"], rec["cache_misses"], rec["cache_hits"]]
print(*out)
"""


def test_cache_hits_and_misses_go_to_their_compile(tmp_path):
    r = subprocess.run([sys.executable, "-c", CACHE_SCRIPT,
                        str(tmp_path / "cache")], capture_output=True,
                       text=True, timeout=120)
    assert r.returncode == 0, r.stderr[-2000:]
    assert r.stdout.split() == ["1", "1", "0", "1", "0", "1"]


def _host_events(xplane, prefixes):
    """(name, start, end) of host events whose name starts with a prefix,
    and of the XLA operations the CPU backend ran."""
    pd = jax.profiler.ProfileData.from_file(xplane)
    spans, ops = [], []
    for plane in pd.planes:
        if not plane.name.startswith("/host:"):
            continue
        for ln in plane.lines:
            for e in ln.events:
                if e.name.startswith(prefixes):
                    spans.append((e.name, e.start_ns, e.end_ns))
                elif (ln.name.startswith("tf_XLA") and "fusion" in e.name):
                    ops.append((e.name, e.start_ns, e.end_ns))
    return spans, ops


def test_train_spans_share_the_device_clock(tmp_path):
    from repro.configs import ARCHS, reduced
    from repro.launch.train import train
    cfg = reduced(ARCHS["olmo-1b"])
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    with jax.profiler.trace(str(tmp_path), profiler_options=opts):
        _, _, step_s = train(cfg, steps=2, batch=2, seq=16, strategy="hier",
                             log_every=100)
    xplane = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    spans, ops = _host_events(xplane[0], ("smlt.",))
    names = [n for n, _, _ in spans]
    for name in ("smlt.train.step", "smlt.train.put", "smlt.train.dispatch",
                 "smlt.train.loss_read", "smlt.data.next_batch"):
        assert names.count(name) == 2, name
    steps = sorted(s for s in spans if s[0] == "smlt.train.step")
    # step_s is each step span's duration; its trace event encloses it
    for (_, s, e), secs in zip(steps, step_s):
        assert secs <= (e - s) / 1e9 < secs + 1e-3
    # every child span lies inside a step span
    for n, s, e in spans:
        if n != "smlt.train.step":
            assert any(s0 <= s and e <= e0 for _, s0, e0 in steps), n
    # one clock: each dispatch starts while the XLA operations run (the
    # parameters' init before it, the step's own after it)
    assert ops
    first, last = min(s for _, s, _ in ops), max(e for _, _, e in ops)
    for _, s, _ in (x for x in spans if x[0] == "smlt.train.dispatch"):
        assert first < s < last


def _delta(before, after, name, key="count"):
    zero = {"count": 0, "total_s": 0.0, "self_s": 0.0, "max_s": 0.0}
    return (after["spans"].get(name, zero)[key]
            - before["spans"].get(name, zero)[key])


def test_generate_spans_give_prefill_and_decode_times():
    import numpy as np
    from repro.configs import ARCHS, reduced
    from repro.serving.engine import ServingEngine
    eng = ServingEngine(reduced(ARCHS["olmo-1b"]), seed=0)
    prompts = np.random.RandomState(0).randint(0, 900, size=(2, 8))
    before = obs.snapshot()
    g = eng.generate(eng.batch_inputs(prompts), 5)
    after = obs.snapshot()
    assert _delta(before, after, "smlt.serve.prefill") == 1
    assert _delta(before, after, "smlt.serve.decode") == 1
    assert _delta(before, after, "smlt.serve.decode_step") == 4
    assert (after["counters"]["serve.tokens"]
            - before["counters"].get("serve.tokens", 0)) == 2 * 5
    assert _delta(before, after, "smlt.serve.prefill", "total_s") == \
        pytest.approx(g.prefill_s, abs=1e-9)
    assert _delta(before, after, "smlt.serve.decode", "total_s") == \
        pytest.approx(g.decode_s, abs=1e-9)


@pytest.mark.parametrize("plan", ["scatter_reduce", "ps", "hier"])
def test_pool_step_spans(plan):
    from repro.serverless import LocalWorkerPool, ParamStore
    params = {"w": jnp.ones((6, 3))}
    batch = {"x": jnp.arange(24.0).reshape(8, 3) / 24}

    def grad_fn(p, b):
        return jax.grad(lambda q: jnp.mean((b["x"] @ q["w"].T) ** 2))(p)

    pool = LocalWorkerPool(grad_fn, 4, ParamStore(), plan=plan)
    before = obs.snapshot()
    g = pool.step(params, batch)
    after = obs.snapshot()
    assert _delta(before, after, "smlt.pool.grads") == 4
    assert _delta(before, after, "smlt.pool.to_host") == 4
    assert _delta(before, after, "smlt.pool.aggregate") == 1
    assert _delta(before, after, "smlt.pool.store") >= 2
    # the store's spans nest inside the aggregation's: its self time is
    # what remains of its duration
    agg = (_delta(before, after, "smlt.pool.aggregate", "total_s")
           - _delta(before, after, "smlt.pool.store", "total_s"))
    assert _delta(before, after, "smlt.pool.aggregate", "self_s") == \
        pytest.approx(agg, abs=1e-6)
    np_g = jax.grad(lambda q: jnp.mean((batch["x"] @ q["w"].T) ** 2))(params)
    assert jnp.allclose(g["w"], np_g["w"], atol=1e-6)
