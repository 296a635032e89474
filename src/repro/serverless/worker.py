"""SMLT worker model (paper Section 4.2).

Two execution paths share the same interfaces:

 - **Analytic path** (paper-scale models, e.g. BERT-medium x 200 workers):
   per-iteration compute/communication times from a calibrated workload
   model. The communication schedule is a ``repro.core.comm.CommPlan``
   priced in closed form with per-phase fan-in contention. This is what
   the paper-figure benchmarks use.
 - **Semantic path** (``LocalWorkerPool``): n logical workers each compute
   real JAX gradients on their minibatch slice and synchronize through the
   (simulated) stores with real numpy payloads — the plan's *strategy*
   selects matching numerics (shard aggregation, tree means, top-k +
   error-feedback sparse sync), used by tests/examples to prove the
   synchronization is exactly equivalent to full-batch all-reduce.
"""
from __future__ import annotations

import dataclasses
from typing import Callable, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.core.rng import base_stream
from repro.core.comm import (CommLike, CommPlan, build_plan,
                             overlap_iteration_time, plan_times)
from repro.serverless.backends import BackendLike, resolve_backend
from repro.serverless.platform import FleetSpec, fn_gflops, fn_net_gbps
from repro.serverless.stores import ObjectStore, ParamStore

# ---------------------------------------------------------------------------
# analytic workload model
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class Workload:
    """Calibrated description of one training task (paper Section 5.1)."""
    name: str
    param_count: int
    flops_per_sample: float          # fwd+bwd FLOPs per training sample
    sample_bytes: float              # bytes of one training sample
    dataset_samples: int
    extra_upload_bytes: float = 0.0  # e.g. Atari RL simulation data

    @property
    def grad_bytes(self) -> float:
        return 4.0 * self.param_count  # f32 gradients


# Paper benchmark models (Section 5.1)
WORKLOADS = {
    "resnet18": Workload("resnet18", 11_000_000, 5.4e9, 150e3, 1_281_167),
    "resnet50": Workload("resnet50", 23_000_000, 12.0e9, 150e3, 1_281_167),
    "bert-small": Workload("bert-small", 66_000_000, 5.1e10, 2_048, 1_000_000),
    "bert-medium": Workload("bert-medium", 110_000_000, 8.4e10, 2_048, 1_000_000),
    "atari-rl": Workload("atari-rl", 50_000_000, 4.0e10, 33_600, 50_000_000,
                         extra_upload_bytes=4.0 * 50_000_000),
}


def compute_time(w: Workload, local_batch: float, memory_mb: float,
                 gflops: Optional[float] = None) -> float:
    """Per-iteration compute seconds; ``gflops`` overrides the
    memory-derived function rate (VM/GPU backends have flat rates)."""
    rate = gflops if gflops is not None else fn_gflops(memory_mb)
    return w.flops_per_sample * local_batch / (rate * 1e9)


def fleet_local_batches(fleet: FleetSpec, global_batch: int) -> List[float]:
    """Load-aware shard placement: the global batch splits in proportion
    to each worker's compute rate, so every worker's compute time is the
    same ``flops * global_batch / sum(rates)`` — the mixed fleet stops
    paying the bsp barrier at its slowest worker's *compute* (network
    caps remain per-worker). Exactly the equal split for homogeneous
    fleets."""
    rates = [fn_gflops(m) for m in fleet.memories]
    total = sum(rates)
    return [global_batch * r / total for r in rates]


def comm_breakdown(scheme: CommLike, grad_bytes: float, n_workers: int,
                   memory_mb: float, param_store: ParamStore,
                   object_store: ObjectStore,
                   n_shards: Optional[int] = None,
                   extra_upload_bytes: float = 0.0,
                   topk_ratio: float = 0.05,
                   fn_net_override_gbps: Optional[float] = None
                   ) -> Dict[str, float]:
    """Static per-phase times of the communication plan. Each phase runs
    with its own ``fan_in`` workers contending (the event engine relaxes
    this to *actual* overlap). ``fn_net_override_gbps`` replaces the
    memory-derived per-function bandwidth — the mixed-fleet approximation
    passes the *narrowest* worker's pipe (a barriered exchange is bound
    by it)."""
    fn_net = (fn_net_override_gbps if fn_net_override_gbps is not None
              else fn_net_gbps(memory_mb))
    fn_bw = fn_net * 8  # not a bottleneck vs store; keep wide
    plan = build_plan(scheme, grad_bytes, n_workers, n_shards=n_shards,
                      extra_upload_bytes=extra_upload_bytes,
                      topk_ratio=topk_ratio)
    times, _busy = plan_times(plan, param_store, object_store, fn_bw)
    return times


def iteration_time(w: Workload, scheme: CommLike, n_workers: int,
                   memory_mb: float, global_batch: int,
                   param_store: ParamStore, object_store: ObjectStore, *,
                   fleet: Optional[FleetSpec] = None,
                   backend: BackendLike = None) -> Dict[str, float]:
    """Closed-form per-iteration time. With a ``fleet``, the mixed-memory
    approximation the Bayesian optimizer probes with: load-aware batch
    placement makes compute ``flops * batch / sum(worker rates)`` (exact,
    since every worker finishes its proportional slice together), while
    synchronization keeps the min-bandwidth bound (narrowest worker's
    pipe). Besides ``compute``/``comm``/``total`` and the per-phase
    entries, the breakdown carries ``store_busy`` — the seconds the
    param store is held by transfers (the keep-alive billing basis,
    which excludes any decompress CPU in ``comm`` and every
    object-store phase). A pipelined plan (``pipeline_depth > 1``)
    prices the iteration as ``max(compute, hidden comm) + exposed comm
    + bubble`` — the overlappable uploads hide under segmented compute
    — and reports the split under ``comm_hidden`` / ``comm_exposed`` /
    ``bubble`` (``comm`` stays the total communication *work*, hidden
    or not; ``store_busy`` is likewise unchanged by overlap, since a
    hidden transfer still holds the store)."""
    n_workers = len(fleet) if fleet is not None else n_workers
    spec = resolve_backend(backend)
    if spec is not None:
        # VM-kind backend: a flat per-worker compute rate and NIC make
        # the fleet homogeneous regardless of the memory tiers
        local_batch = max(global_batch // n_workers, 1)
        comp = compute_time(w, local_batch, memory_mb,
                            gflops=spec.gflops_for(memory_mb))
        net_override = spec.net_gbps_for(memory_mb)
    elif fleet is None or fleet.is_homogeneous:
        mem = fleet.memories[0] if fleet is not None else memory_mb
        local_batch = max(global_batch // n_workers, 1)
        comp = compute_time(w, local_batch, mem)
        net_override = None if fleet is None else fleet.min_net_gbps()
    else:
        comp = (w.flops_per_sample * global_batch
                / (fleet.gflops_total() * 1e9))
        net_override = fleet.min_net_gbps()
    fn_net = (net_override if net_override is not None
              else fn_net_gbps(memory_mb))
    plan = build_plan(scheme, w.grad_bytes, n_workers,
                      extra_upload_bytes=w.extra_upload_bytes)
    comm, store_busy = plan_times(plan, param_store, object_store, fn_net * 8)
    hidden_names = {ph.name for ph in plan.overlappable_phases}
    hidden = sum(t for name, t in comm.items() if name in hidden_names)
    exposed = sum(comm.values()) - hidden
    ov = overlap_iteration_time(comp, hidden, exposed, plan.pipeline_depth)
    return {"compute": comp, "comm": sum(comm.values()),
            "total": ov["total"], "store_busy": store_busy,
            "comm_hidden": ov["comm_hidden"],
            "comm_exposed": ov["comm_exposed"], "bubble": ov["bubble"],
            **comm}


# ---------------------------------------------------------------------------
# gradient sharding math (shared by simulator + semantic path + tests)
# ---------------------------------------------------------------------------


def flatten_grads(grads) -> np.ndarray:
    leaves = jax.tree.leaves(grads)
    return np.concatenate([np.asarray(x, dtype=np.float32).ravel()
                           for x in leaves])


def unflatten_grads(flat: np.ndarray, grads_like):
    leaves, treedef = jax.tree.flatten(grads_like)
    out, off = [], 0
    for leaf in leaves:
        n = int(np.prod(leaf.shape)) if leaf.shape else 1
        out.append(flat[off:off + n].reshape(leaf.shape).astype(leaf.dtype))
        off += n
    return jax.tree.unflatten(treedef, out)


def make_shards(flat: np.ndarray, m: int) -> List[np.ndarray]:
    """Split a flat gradient into m near-equal shards (shard generator, Fig 5)."""
    pad = (-len(flat)) % m
    if pad:
        flat = np.concatenate([flat, np.zeros(pad, flat.dtype)])
    return list(flat.reshape(m, -1))


def join_shards(shards: List[np.ndarray], size: int) -> np.ndarray:
    return np.concatenate(shards)[:size]


def parse_sync_mode(sync_mode: str, staleness: int = 0):
    """Parse ``"bsp" | "ssp" | "ssp(k)" | "async"`` into (mode, bound).

    bsp is ssp with bound 0; async is ssp with an unbounded window."""
    m = sync_mode.strip().lower()
    if m.startswith("ssp(") and m.endswith(")"):
        return "ssp", int(m[4:-1])
    if m == "bsp":
        return "bsp", 0
    if m == "ssp":
        return "ssp", staleness
    if m == "async":
        return "async", None
    raise ValueError(f"sync_mode {sync_mode!r}")


class LocalWorkerPool:
    """Semantic SMLT: n logical workers with real JAX grads, synchronizing
    via the (simulated) param store under a ``CommPlan``.

    ``plan`` (a ``CommPlan``, ``CommSpec``, or legacy scheme string)
    selects the synchronization *numerics* to match what the simulator
    prices:
      - ``scatter_reduce`` (default; legacy scheme "hier"): workers shard
        their gradients, worker j aggregates shard j from everyone and
        re-uploads it, exactly as Figure 5 prescribes.
      - ``ps``: every worker uploads its full gradient; the mean is taken
        over all n full gradients (Cirrus/Siren pattern).
      - ``hier``: partial sums reduce up a ``branching``-ary tree of
        group aggregators; the root mean is redistributed.
      - a compressed plan (``ratio < 1``): workers upload top-k sparse
        gradients with per-worker error feedback
        (``repro.core.compression``); the aggregator sums the sparse
        contributions. ``ratio=1.0`` keeps every entry — numerically the
        dense mean.
      - a pipelined plan (``pipeline_depth > 1``): each worker computes
        its slice as micro-batched gradient accumulation — the schedule
        the simulator overlaps with the uploads; the weighted
        per-segment mean equals the full-slice gradient, so overlap
        never changes the numerics.

    ``use_kernel=True`` runs the shard aggregation (step 3 of Fig. 5)
    through the Pallas ``hier_agg`` kernel instead of numpy.

    ``sync_mode`` selects the staleness semantics that mirror the event
    engine's timing modes (``repro.serverless.events``):
      - "bsp": every worker's gradient is computed at the current params
        (exactly equivalent to full-batch all-reduce; the seed behavior).
      - "ssp(k)": worker w refreshes its param snapshot every k+1 steps
        (staggered by worker id), so gradients are computed at params at
        most k versions stale — the bounded-staleness numerics.
      - "async": workers refresh on an independent seeded schedule with no
        bound (geometric gaps), the fully-asynchronous numerics.
    """

    def __init__(self, grad_fn: Callable, n_workers: int,
                 param_store: ParamStore, *, use_kernel: bool = False,
                 plan: Optional[CommLike] = None,
                 sync_mode: str = "bsp", staleness: int = 0, seed: int = 0,
                 async_refresh_p: float = 0.5):
        self.grad_fn = grad_fn
        self.n = n_workers
        self.store = param_store
        self.use_kernel = use_kernel
        # the pool only consumes the plan's strategy/ratio/branching and
        # accounts store bytes from the real payloads it moves, so specs
        # and scheme strings bind to a token-size plan (grad bytes are
        # only known per step); a prebuilt plan is taken as-is
        if isinstance(plan, CommPlan):
            if plan.n_workers != n_workers:
                raise ValueError(f"plan built for n={plan.n_workers}, "
                                 f"pool has n={n_workers}")
            self.plan = plan
        else:
            self.plan = build_plan(plan if plan is not None else "hier",
                                   1.0, n_workers)
        self.mode, self.staleness = parse_sync_mode(sync_mode, staleness)
        self.async_refresh_p = async_refresh_p
        self._rng = base_stream(seed)
        self._iter = 0
        self._snaps: List = [None] * n_workers    # stale param snapshots
        self._vers = [0] * n_workers
        self._ef: Dict[int, "ErrorFeedback"] = {}  # compressed path only

    def _worker_params(self, w: int, params):
        """The (possibly stale) params worker ``w`` computes gradients at."""
        if self.mode == "bsp":
            return params
        if self._snaps[w] is None:
            refresh = True
        elif self.mode == "ssp":
            k = self.staleness
            # staggered refresh every k+1 steps; the gap never exceeds k
            refresh = ((self._iter + w) % (k + 1) == 0
                       or self._iter - self._vers[w] > k)
        else:                                      # async: unbounded gaps
            refresh = self._rng.random_sample() < self.async_refresh_p
        if refresh:
            self._snaps[w] = params
            self._vers[w] = self._iter
        return self._snaps[w]

    def _slice_grad(self, params, sl):
        """One worker's gradient on its batch slice. A pipelined plan
        (``pipeline_depth > 1``) computes it as micro-batched gradient
        accumulation — the schedule the simulator overlaps with uploads:
        per-segment gradients are combined with segment-size weights,
        which for a per-batch-mean loss *is* the full-slice gradient, so
        overlap changes the timing model and never the numerics."""
        d = self.plan.pipeline_depth
        rows = jax.tree.leaves(sl)[0].shape[0]
        if d <= 1 or rows < 2:
            return self.grad_fn(params, sl)
        d = min(d, rows)
        bounds = [round(i * rows / d) for i in range(d + 1)]
        acc, total = None, 0
        for a, b in zip(bounds, bounds[1:]):
            if b <= a:
                continue
            micro = jax.tree.map(lambda x: x[a:b], sl)
            g = self.grad_fn(params, micro)
            wgt = float(b - a)
            if acc is None:
                acc = jax.tree.map(lambda x: np.asarray(x, np.float32) * wgt,
                                   g)
            else:
                acc = jax.tree.map(
                    lambda s, x: s + np.asarray(x, np.float32) * wgt, acc, g)
            total += wgt
        return jax.tree.map(lambda s: s / total, acc)

    def _worker_grads(self, params, global_batch):
        """Each worker's flat gradient on its batch slice (stale-aware)."""
        n = self.n
        flats, g_like = [], None
        for w in range(n):
            sl = jax.tree.map(
                lambda x: x[w * (x.shape[0] // n):(w + 1) * (x.shape[0] // n)],
                global_batch)
            with obs.span("smlt.pool.grads"):
                g = self._slice_grad(self._worker_params(w, params), sl)
            with obs.span("smlt.pool.to_host"):
                flats.append(flatten_grads(g))
            g_like = g
        return flats, g_like

    def step(self, params, global_batch) -> Dict:
        """global_batch: dict of arrays with leading dim divisible by n.
        Returns the aggregated (mean) gradient pytree.

        Spans: ``smlt.pool.grads`` (a worker's gradient, dispatched) and
        ``smlt.pool.to_host`` (its flat host copy, which waits for it),
        then ``smlt.pool.aggregate`` around the whole synchronization,
        whose self time is the aggregation arithmetic: the store's puts
        and gets inside it are ``smlt.pool.store`` spans."""
        flats, g_like = self._worker_grads(params, global_batch)
        with obs.span("smlt.pool.aggregate"):
            if self.plan.ratio < 1.0:
                mean_flat = self._step_compressed(flats)
            elif self.plan.strategy == "ps":
                mean_flat = self._step_ps(flats)
            elif self.plan.strategy == "hier":
                mean_flat = self._step_hier(flats)
            else:
                mean_flat = self._step_scatter_reduce(flats)
        self._iter += 1
        return unflatten_grads(mean_flat, g_like)

    # -- strategy numerics ---------------------------------------------------
    def _step_scatter_reduce(self, flats):
        n = self.n
        flat_size = len(flats[0])
        # (1) each worker shards its gradient and uploads the shards
        with obs.span("smlt.pool.store"):
            for w, flat in enumerate(flats):
                for j, s in enumerate(make_shards(flat, n)):
                    self.store.put(f"shard/{w}/{j}", s, nbytes=s.nbytes)
        # (2) worker j aggregates shard j from all workers (mean), re-uploads
        for j in range(n):
            with obs.span("smlt.pool.store"):
                stacked = np.stack([self.store.get(f"shard/{w}/{j}")
                                    for w in range(n)])
            if self.use_kernel:
                from repro.kernels import ops as kops
                agg = np.asarray(kops.aggregate_shards(jnp.asarray(stacked)))
            else:
                agg = stacked.mean(axis=0)
            with obs.span("smlt.pool.store"):
                self.store.put(f"aggr/{j}", agg, nbytes=agg.nbytes)
        # (3) every worker downloads all aggregated shards -> updated model;
        # they are identical, so reconstruct once.
        with obs.span("smlt.pool.store"):
            agg = [self.store.get(f"aggr/{j}") for j in range(n)]
        return join_shards(agg, flat_size)

    def _step_ps(self, flats):
        n = self.n
        with obs.span("smlt.pool.store"):
            for w, flat in enumerate(flats):
                self.store.put(f"grad/{w}", flat, nbytes=flat.nbytes)
        acc = np.zeros(len(flats[0]), np.float32)
        for w in range(n):
            with obs.span("smlt.pool.store"):
                g = self.store.get(f"grad/{w}", nbytes=flats[w].nbytes)
            acc += g
        return acc / n

    def _step_hier(self, flats):
        """Tree aggregation: partial sums reduce level by level through
        the store; the root's sum / n is the exact global mean."""
        n, b = self.n, max(self.plan.branching or 4, 2)
        nbytes = flats[0].nbytes
        partials = list(flats)                   # level-0 partial sums
        lvl = 0
        while len(partials) > 1:
            lvl += 1
            with obs.span("smlt.pool.store"):
                for i, p in enumerate(partials):
                    self.store.put(f"hier/{lvl}/{i}", p, nbytes=nbytes)
            nxt = []
            for g0 in range(0, len(partials), b):
                with obs.span("smlt.pool.store"):
                    members = [self.store.get(f"hier/{lvl}/{i}",
                                              nbytes=nbytes)
                               for i in range(g0, min(g0 + b, len(partials)))]
                nxt.append(sum(members))
            partials = nxt
        root = partials[0]
        with obs.span("smlt.pool.store"):
            self.store.put("hier/root", root, nbytes=nbytes)
            root = self.store.get("hier/root", nbytes=nbytes)
        return root / n

    def _step_compressed(self, flats):
        """Top-k + error feedback: each worker uploads only its k largest
        (corrected) entries; the aggregator sums sparse contributions.
        Wire bytes follow the plan's compressed model (value + index)."""
        from repro.core.compression import ErrorFeedback, compressed_bytes
        n, ratio = self.n, self.plan.ratio
        size = len(flats[0])
        nbytes = compressed_bytes(size, ratio)
        for w, flat in enumerate(flats):
            if w not in self._ef:
                self._ef[w] = ErrorFeedback.init(size)
            idx, vals = self._ef[w].compress(flat, ratio)
            with obs.span("smlt.pool.store"):
                self.store.put(f"sparse/{w}", (idx, vals), nbytes=nbytes)
        acc = np.zeros(size, np.float32)
        for w in range(n):
            with obs.span("smlt.pool.store"):
                idx, vals = self.store.get(f"sparse/{w}", nbytes=nbytes)
            acc[idx] += vals
        return acc / n
