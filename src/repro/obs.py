"""The program's own spans, counters and compile events, kept in memory.

    with obs.span("smlt.train.step") as sp:    # host span, profiler clock
        ...
    sp.seconds                                 # this span's duration
    obs.count("serve.tokens", n)               # a counter
    obs.build("train_step")                    # a new build of a jitted fn
    obs.snapshot()                             # everything, as plain dicts

    @obs.scoped("mlp")                         # device side: a named scope
    def apply_mlp(...): ...

A span opens a ``jax.profiler.TraceAnnotation`` of its name, so under
``jax.profiler.trace`` it sits on the host plane on the device's clock;
with the profiler off it costs about 2.3 us on a TPU v5e host. Host span
names start with ``smlt.`` (the benchmark keeps ``bench.`` for its own
spans). Each name keeps a count, total, self time (the duration less the
part covered by child spans on the same thread) and max.

``scoped(name)`` puts a function's operations under ``jax.named_scope``:
HLO op metadata only (``op_name`` reads ``.../mlp/dot_general``, and
``transpose(jvp(mlp))`` for the backward pass), so XLA fuses and schedules
as before, and the device trace can name the layer.

Compile events come from ``jax.monitoring``: per jitted function name, the
backend compiles (a compile or a persistent-cache load), their seconds, the
seconds of tracing and of lowering to MLIR, and the persistent-cache hits
and misses charged to the backend compile they belong to. The listener is
registered when this module is first imported; ``launch/steps.py`` imports
it, so it is in place before any step compiles.
"""
from __future__ import annotations

import collections
import functools
import re
import threading
import time
from typing import Dict

import jax
from jax import monitoring
from jax.profiler import TraceAnnotation

_BACKEND = "/jax/core/compile/backend_compile_duration"
_DURATIONS = {_BACKEND: "compile_s",
              "/jax/core/compile/jaxpr_trace_duration": "trace_s",
              "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower_s"}
_CACHE = {"/jax/compilation_cache/cache_hits": "cache_hits",
          "/jax/compilation_cache/cache_misses": "cache_misses"}
_WRAPPED = re.compile(r"^\w+\((.*)\)$")   # "jit(train_step)" -> "train_step"

_lock = threading.Lock()
_local = threading.local()
_spans: Dict[str, list] = {}              # name -> [count, total, self, max] ns
_counters: Dict[str, int] = collections.Counter()
_compiles: Dict[str, dict] = {}           # fun name -> since import
_last_build: Dict[str, dict] = {}         # fun name -> since its last build()


def _stack() -> list:
    stack = getattr(_local, "stack", None)
    if stack is None:
        stack = _local.stack = []
    return stack


class span:
    """Context manager timing one interval under ``name``; ``seconds`` is
    its duration once it has closed."""
    __slots__ = ("name", "ns", "_t0", "_child_ns", "_ann")

    def __init__(self, name: str):
        self.name = name
        self.ns = 0

    def __enter__(self) -> "span":
        _stack().append(self)
        self._child_ns = 0
        self._ann = TraceAnnotation(self.name)
        self._ann.__enter__()
        self._t0 = time.perf_counter_ns()
        return self

    def __exit__(self, *exc) -> None:
        self.ns = dur = time.perf_counter_ns() - self._t0
        self._ann.__exit__(*exc)
        stack = _stack()
        stack.pop()
        if stack:
            stack[-1]._child_ns += dur
        with _lock:
            agg = _spans.setdefault(self.name, [0, 0, 0, 0])
            agg[0] += 1
            agg[1] += dur
            agg[2] += dur - self._child_ns
            agg[3] = max(agg[3], dur)

    @property
    def seconds(self) -> float:
        return self.ns / 1e9


def scoped(name: str):
    """Decorator: run the function under a fresh ``jax.named_scope(name)``
    per call (one shared scope object is not safe to re-enter)."""
    def wrap(fn):
        @functools.wraps(fn)
        def inner(*args, **kwargs):
            with jax.named_scope(name):
                return fn(*args, **kwargs)
        return inner
    return wrap


def count(name: str, n: int = 1) -> None:
    with _lock:
        _counters[name] += n


def _record() -> dict:
    return {"compiles": 0, "cache_hits": 0, "cache_misses": 0,
            "compile_s": 0.0, "trace_s": 0.0, "lower_s": 0.0}


def build(fun_name: str) -> None:
    """A new jitted ``fun_name`` was built: counts ``<fun_name>.builds`` and
    starts its ``last_build`` compile record afresh."""
    with _lock:
        _counters[f"{fun_name}.builds"] += 1
        _last_build[fun_name] = _record()


def _on_event(event: str, **_) -> None:
    key = _CACHE.get(event)
    if key is not None:      # charged to the backend compile that follows
        pending = getattr(_local, "cache", None) or collections.Counter()
        _local.cache = pending
        pending[key] += 1


def _on_duration(event: str, secs: float, fun_name: str = "", **_) -> None:
    key = _DURATIONS.get(event)
    if key is None:
        return
    m = _WRAPPED.match(fun_name)
    name = m.group(1) if m else fun_name
    pending = {}
    if event == _BACKEND:
        pending, _local.cache = getattr(_local, "cache", None) or {}, None
    with _lock:
        for rec in (_compiles.setdefault(name, _record()),
                    _last_build.get(name)):
            if rec is None:
                continue
            rec[key] += secs
            rec["compiles"] += event == _BACKEND
            for k, n in pending.items():
                rec[k] += n


_registered = False


def _register() -> None:
    global _registered
    if not _registered:
        monitoring.register_event_listener(_on_event)
        monitoring.register_event_duration_secs_listener(_on_duration)
        _registered = True


def snapshot() -> dict:
    """All spans (seconds), counters and compile records, as plain dicts."""
    with _lock:
        return {
            "spans": {k: {"count": c, "total_s": t / 1e9, "self_s": s / 1e9,
                          "max_s": mx / 1e9}
                      for k, (c, t, s, mx) in _spans.items()},
            "counters": dict(_counters),
            "compiles": {k: dict(v) for k, v in _compiles.items()},
            "last_build": {k: dict(v) for k, v in _last_build.items()},
        }


_register()
