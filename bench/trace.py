"""Reduction of one profiler trace to the numbers the metric readers use.

``load`` reads the ``.xplane.pb`` that ``jax.profiler`` writes and returns
plain events; ``reduce`` turns them into a ``Summary``. Everything here is
arithmetic on intervals, so ``tests/test_bench_trace.py`` checks it on a
small trace recorded on the CPU and on hand-made events.

Device operations come from each device plane's ``XLA Ops`` line
(``/device:TPU:<n>``). A trace recorded on the CPU has no device plane; its
XLA operations run on the host's XLA threads and count as one device, which
is what the CPU test reads. Host spans are the benchmark's own
``jax.profiler.TraceAnnotation`` events, whose names start with ``bench.``.
"""
from __future__ import annotations

import collections
import dataclasses
import glob
import os
import re
from typing import Dict, List, Optional, Tuple

# collective operations as XLA names them, synchronous or async halves
COLLECTIVE = re.compile(
    r"all-reduce|reduce-scatter|all-gather|all-to-all|collective-permute"
    r"|reducescatter|allreduce|allgather", re.IGNORECASE)
SPAN_PREFIX = "bench."
CPU_OP_LINES = ("tf_XLAPjRtCpuClient", "tf_XLAEigen")
CPU_NOT_OPS = ("ThreadpoolListener", "end: ", "ThunkExecutor", "SlinkyThreadPool")

Event = Tuple[str, float, float]  # (name, start_ns, end_ns)


@dataclasses.dataclass
class Trace:
    devices: Dict[str, List[Event]]   # device name -> its operations
    spans: List[Event]                # the benchmark's host spans


@dataclasses.dataclass
class Summary:
    window_s: float                   # first span start to last span end
    busy_s: float                     # mean over devices of the busy union
    collective_s: float               # device 0: summed collective time
    exposed_collective_s: float       # device 0: collectives with no other op
    device_ops: List[Tuple[str, float]]   # device 0: top ops by time
    idle_gaps: List[Tuple[str, float]]    # device 0: idle time by host span
    n_devices: int


def find_xplane(trace_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return paths[-1]


def load(path: str) -> Trace:
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    devices: Dict[str, List[Event]] = {}
    spans: List[Event] = []
    cpu_ops: List[Event] = []
    for plane in pd.planes:
        if plane.name.startswith("/device:"):
            lines = {ln.name: ln for ln in plane.lines}
            ops = lines.get("XLA Ops")
            if ops is None:
                continue
            devices[plane.name] = [(e.name, e.start_ns, e.end_ns)
                                   for e in ops.events]
        elif plane.name.startswith("/host:"):
            for ln in plane.lines:
                for e in ln.events:
                    if e.name.startswith(SPAN_PREFIX):
                        spans.append((e.name, e.start_ns, e.end_ns))
                    elif (ln.name.startswith(CPU_OP_LINES)
                          and not e.name.startswith(CPU_NOT_OPS)
                          and e.duration_ns > 0):
                        cpu_ops.append((e.name, e.start_ns, e.end_ns))
    if not devices and cpu_ops:
        devices["/host:CPU"] = cpu_ops
    return Trace(devices=devices, spans=sorted(spans, key=lambda e: e[1]))


def union(intervals) -> List[Tuple[float, float]]:
    """Merge (start, end) intervals into disjoint sorted ones."""
    out: List[List[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def clip(intervals, lo: float, hi: float) -> List[Tuple[float, float]]:
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if min(e, hi) > max(s, lo)]


def length(intervals) -> float:
    return sum(e - s for s, e in intervals)


def subtract(a, b) -> List[Tuple[float, float]]:
    """Parts of the disjoint sorted intervals ``a`` not covered by ``b``."""
    b = union(b)
    out = []
    for s, e in a:
        cur = s
        for bs, be in b:
            if be <= cur or bs >= e:
                continue
            if bs > cur:
                out.append((cur, bs))
            cur = max(cur, be)
            if cur >= e:
                break
        if cur < e:
            out.append((cur, e))
    return out


def _span_at(spans: List[Event], t: float) -> str:
    """The innermost (latest-starting) host span that contains time t."""
    best: Optional[Event] = None
    for sp in spans:
        if sp[1] <= t <= sp[2] and (best is None or sp[1] >= best[1]):
            best = sp
    return best[0] if best else "no_span"


def reduce(tr: Trace, top: int = 10) -> Summary:
    if not tr.devices:
        raise ValueError("the trace holds no device operation")
    if not tr.spans:
        raise ValueError("the trace holds no bench.* host span")
    lo = min(s for _, s, _ in tr.spans)
    hi = max(e for _, _, e in tr.spans)
    window_ns = hi - lo
    names = sorted(tr.devices)
    busy = [length(union(clip([(s, e) for _, s, e in tr.devices[n]], lo, hi)))
            for n in names]

    ops0 = [(n, s, e) for n, s, e in tr.devices[names[0]]
            if min(e, hi) > max(s, lo)]
    coll = union(clip([(s, e) for n, s, e in ops0 if COLLECTIVE.search(n)],
                      lo, hi))
    other = union(clip([(s, e) for n, s, e in ops0
                        if not COLLECTIVE.search(n)], lo, hi))
    exposed = subtract(coll, other)

    per_op = collections.Counter()
    for n, s, e in ops0:
        per_op[n] += min(e, hi) - max(s, lo)
    busy0 = union(clip([(s, e) for _, s, e in ops0], lo, hi))
    gaps = subtract([(lo, hi)], busy0)
    per_span = collections.Counter()
    cuts = sorted({t for _, a, b in tr.spans for t in (a, b)})
    for s, e in gaps:
        # split the gap where a host span starts or ends
        edges = [s] + [t for t in cuts if s < t < e] + [e]
        for a, b in zip(edges, edges[1:]):
            per_span[_span_at(tr.spans, (a + b) / 2)] += b - a

    return Summary(
        window_s=window_ns * 1e-9,
        busy_s=sum(busy) / len(busy) * 1e-9,
        collective_s=length(coll) * 1e-9,
        exposed_collective_s=length(exposed) * 1e-9,
        device_ops=[(n, t * 1e-9) for n, t in per_op.most_common(top)],
        idle_gaps=[(n, t * 1e-9) for n, t in per_span.most_common(top)],
        n_devices=len(names))
