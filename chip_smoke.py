"""Chip smoke test: SMLT's device path, end to end, on a TPU.

    python chip_smoke.py               # one chip: train, serve, aggregate
    python chip_smoke.py --four-chips  # four chips: gradient sync only

One chip runs three phases through the entry points a user calls, at
olmo-1b's published widths (all 16 layers) with random weights from a seed:

  train      ``launch.train.train``: a few ``hier`` steps at 4 x 1024
             tokens, remat on; losses must be finite.
  serve      ``ServingEngine``: 4 x 512-token prompts, 16 new tokens; the
             last decode step's logits must match a prefill over the prompt
             plus the generated tokens.
  aggregate  ``kernels.ops.aggregate_shards`` compiled for the chip on 8
             workers' shards of olmo-1b's flat f32 gradient, against
             ``jnp.mean``; then one ``LocalWorkerPool(use_kernel=True)``
             step against the numpy aggregation.

``--four-chips`` runs only the sync phase on a (4, 1) data mesh: one train
step under ``hier`` and under ``allreduce`` from the same state and batch,
``hier_sync.make_sync_grad_fn``'s ScatterReduce against its all-reduce, and
one ``hier`` step at 4 x 4096 tokens, which fits only because the
optimizer state is sharded.

Step and phase times printed here are smoke timings on the host clock,
not measurements. Every phase drops its device arrays before the next
starts. Any failure raises, so the process exits non-zero; the last line
of stdout, printed only after every phase passed, is one JSON object:
{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": ...}}.
"""
from __future__ import annotations

import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import time  # noqa: E402

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
from jax.sharding import Mesh, NamedSharding  # noqa: E402
from jax.sharding import PartitionSpec as P  # noqa: E402

from repro.configs import ARCHS  # noqa: E402
from repro.core.hier_sync import make_sync_grad_fn  # noqa: E402
from repro.core.rng import base_stream  # noqa: E402
from repro.data import DataConfig, TokenDataset  # noqa: E402
from repro.kernels import ops, resolve_interpret  # noqa: E402
from repro.launch.compile_cache import enable_compile_cache  # noqa: E402
from repro.launch.steps import make_train_step  # noqa: E402
from repro.launch.train import init_state, train  # noqa: E402
from repro.models import registry  # noqa: E402
from repro.optim import AdamW  # noqa: E402
from repro.serverless import LocalWorkerPool, ParamStore  # noqa: E402
from repro.serving import ServingEngine  # noqa: E402

OLMO = ARCHS["olmo-1b"].replace(remat=True)
# bf16 keeps 8 significant bits: neighbouring values are 2^-7 apart in
# [1, 2), so rounding moves a value by at most 2^-8 of itself
BF16_ROUND = 2.0 ** -8
F32_EPS = float(np.finfo(np.float32).eps)

# Serve: the last decode step's logits against a prefill over the same
# tokens, as max|diff| over the largest |logit|. Both paths round every
# layer's activations to bf16, but at different places (one query against
# the KV cache vs. blockwise over the whole sequence), so one-step (2^-7)
# differences enter each of the 16 layers and carry through the residual
# stream. 2^-4 leaves room for 8 bf16 steps; a wrong cache position or a
# stale cache entry moves logits by O(1) of their scale.
SERVE_TOL = 2.0 ** -4
# Four chips: the synchronized gradient of two strategies, per leaf, as
# max|diff| over the leaf's largest |value|. Four bf16 worker gradients
# summed in another order differ by a few bf16 ulps of the partial sums,
# which exceed the mean where workers' gradients cancel; 2^-5 leaves room
# for 4 bf16 steps. A missed reduction moves it by O(1).
SYNC_TOL = 2.0 ** -5
# Loss of one forward pass whose only difference is the sync strategy.
LOSS_RTOL = 1e-3


def check(cond: bool, what: str):
    if not cond:
        raise AssertionError(what)


def peak_bytes(device=None):
    """Peak device bytes in use, where the backend reports it."""
    stats = (device or jax.devices()[0]).memory_stats()
    return stats.get("peak_bytes_in_use") if stats else None


def drop_arrays(phase: str):
    """Collect what the phase left and prove its arrays are gone."""
    gc.collect()
    live = sum(x.nbytes for x in jax.live_arrays())
    print(f"[{phase}] live device bytes after phase: {live}", flush=True)
    check(live < 2 ** 30, f"{phase} left {live} bytes on the device")


def token_batch(cfg, batch: int, seq: int, seed: int = 0):
    toks = TokenDataset(DataConfig(vocab_size=cfg.vocab_size, seq_len=seq,
                                   seed=seed)).sample(0, 0, batch, seq)
    return {"tokens": toks, "labels": toks}


# ---------------------------------------------------------------------------
# one chip
# ---------------------------------------------------------------------------


def phase_train(cfg, *, steps: int, batch: int, seq: int):
    t0 = time.perf_counter()
    _, losses, step_s = train(cfg, steps=steps, batch=batch, seq=seq,
                              strategy="hier", log_every=1)
    check(all(math.isfinite(x) for x in losses), f"train losses {losses}")
    print(f"[train] {cfg.arch_id} {cfg.n_layers}L d={cfg.d_model} "
          f"{batch}x{seq} tokens, losses {losses}", flush=True)
    print(f"[train] first step (compile included) {step_s[0]:.3f} s; "
          f"smoke timing of later steps, not a measurement: "
          f"{[round(s, 4) for s in step_s[1:]]} s", flush=True)
    print(f"[train] peak_bytes_in_use {peak_bytes()}; phase "
          f"{time.perf_counter() - t0:.1f} s", flush=True)
    return losses


def phase_serve(cfg, *, n_requests: int, prompt_len: int, gen: int,
                seed: int = 0):
    t0 = time.perf_counter()
    engine = ServingEngine(cfg, seed=seed)
    prompts = base_stream(seed).randint(
        0, cfg.vocab_size, size=(n_requests, prompt_len)).astype(np.int32)
    out = engine.generate(engine.batch_inputs(prompts), gen)
    # the last decode step consumed generated token gen-2 at position
    # prompt_len+gen-2: a prefill over prompt + tokens[:-1] ends there too
    full = np.concatenate([prompts, np.asarray(out.tokens[:, :-1])], axis=1)
    ref, _ = engine.prefill(engine.batch_inputs(full), full.shape[1])
    ref = ref[:, -1:, :cfg.vocab_size].astype(jnp.float32)
    got = out.last_logits[:, :, :cfg.vocab_size].astype(jnp.float32)
    err = float(jnp.max(jnp.abs(got - ref)))
    scale = float(jnp.max(jnp.abs(ref)))
    print(f"[serve] {n_requests}x{prompt_len} prompts, {gen} new tokens: "
          f"decode vs prefill logits max|diff| {err:.6g}, max|logit| "
          f"{scale:.6g}, ratio {err / scale:.6g} (tolerance {SERVE_TOL})",
          flush=True)
    check(math.isfinite(err) and err <= SERVE_TOL * scale,
          f"decode logits differ from prefill by {err} (scale {scale})")
    print(f"[serve] smoke timing, not a measurement: first token "
          f"{out.prefill_s:.3f} s (compile included), {gen - 1} decode "
          f"steps {out.decode_s:.3f} s; peak_bytes_in_use {peak_bytes()}; "
          f"phase {time.perf_counter() - t0:.1f} s", flush=True)
    return err / scale


def phase_aggregate(*, n_workers: int, shard_len: int, seed: int = 0):
    t0 = time.perf_counter()
    shards = jax.jit(lambda k: jax.random.normal(
        k, (n_workers, shard_len), jnp.float32))(jax.random.key(seed))
    compiled = ops.aggregate_shards.lower(shards).compile()
    mosaic = "tpu_custom_call" in compiled.as_text()
    check(mosaic == (not resolve_interpret()),
          f"aggregate_shards compiled={mosaic} on {jax.default_backend()}")
    got = compiled(shards)
    want = jnp.mean(shards, axis=0)
    err = float(jnp.max(jnp.abs(got - want)))
    # n f32 addends in two orders differ by < n roundings of sums <= n*max|x|
    tol = n_workers * F32_EPS * float(jnp.max(jnp.abs(shards)))
    print(f"[aggregate] {n_workers} x {shard_len} f32 shards "
          f"({shards.nbytes} bytes), Mosaic kernel: {mosaic}; max|kernel - "
          f"jnp.mean| {err:.6g} (tolerance {tol:.6g})", flush=True)
    check(err <= tol, f"aggregate_shards differs from jnp.mean by {err}")
    del shards, got, want

    # the worker's path: Fig. 5 step 3 through the platform-picked kernel
    rng = base_stream(seed)
    params = {"w": jnp.asarray(rng.randn(64, 32), jnp.float32)}
    batch = {"x": jnp.asarray(rng.randn(8 * n_workers, 64), jnp.float32),
             "y": jnp.asarray(rng.randn(8 * n_workers, 32), jnp.float32)}
    grad = jax.jit(jax.grad(
        lambda p, b: jnp.mean((b["x"] @ p["w"] - b["y"]) ** 2)))
    g_np = LocalWorkerPool(grad, n_workers, ParamStore()).step(params, batch)
    g_k = LocalWorkerPool(grad, n_workers, ParamStore(),
                          use_kernel=True).step(params, batch)
    a, b = np.asarray(g_k["w"]), np.asarray(g_np["w"])
    err = float(np.max(np.abs(a - b)))
    tol = n_workers * F32_EPS * float(np.max(np.abs(b)))
    print(f"[aggregate] LocalWorkerPool(use_kernel=True) step, {n_workers} "
          f"workers: max|kernel - numpy| {err:.6g} (tolerance {tol:.6g}); "
          f"peak_bytes_in_use {peak_bytes()}; phase "
          f"{time.perf_counter() - t0:.1f} s", flush=True)
    check(err <= tol, f"pool kernel aggregation differs by {err}")
    return err


# ---------------------------------------------------------------------------
# four chips
# ---------------------------------------------------------------------------


def _shard_layout(x):
    return sorted((s.device.id, tuple(s.data.shape))
                  for s in x.addressable_shards)


def _check_spread(name: str, x, n: int, split: bool):
    """``x`` lives on n devices, split n ways on one dim or whole on each."""
    layout = _shard_layout(x)
    print(f"[sync] {name} {tuple(x.shape)} -> (device, shard) {layout}",
          flush=True)
    check(len({d for d, _ in layout}) == n, f"{name} not on {n} devices")
    want = x.size // n if split else x.size
    check(all(math.prod(s) == want for _, s in layout),
          f"{name} shards {layout} are not {'split' if split else 'whole'}")


def _largest(tree):
    return max(jax.tree.leaves(tree), key=lambda x: x.size)


def _train_step_once(cfg, mesh, strategy, opt, batch_np):
    step, pshard, oshard, bshard_fn = make_train_step(
        cfg, mesh, strategy=strategy, optimizer=opt)
    params, opt_state = init_state(cfg, opt, pshard, oshard)
    batch = jax.device_put(batch_np, bshard_fn(batch_np))
    n = mesh.devices.size
    _check_spread(f"{strategy} batch tokens", batch["tokens"], n, True)
    _check_spread(f"{strategy} largest param", _largest(params), n, False)
    _check_spread(f"{strategy} largest Adam moment", _largest(opt_state.mu),
                  n, strategy != "allreduce")
    t0 = time.perf_counter()
    params, opt_state, loss = step(params, opt_state, batch)
    loss = float(loss)
    print(f"[sync] {strategy} step {batch_np['tokens'].shape}: loss "
          f"{loss:.6f}, {time.perf_counter() - t0:.3f} s (compile included, "
          f"smoke timing); peak_bytes_in_use per device "
          f"{[peak_bytes(d) for d in mesh.devices.flat]}", flush=True)
    return loss, jax.device_get(params), jax.device_get(opt_state.mu)


def _update_excess(a, b):
    """max over one leaf of |a - b| - 2 * BF16_ROUND * max(|a|, |b|): the
    gap between two bf16 results beyond what rounding each can add."""
    a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
    return float(np.max(np.abs(a - b) - 2 * BF16_ROUND
                        * np.maximum(np.abs(a), np.abs(b))))


def _leaf_ratio(a, b):
    """max|a - b| / max|b| over one leaf, in f32 on the host."""
    a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
    return float(np.max(np.abs(a - b))) / max(float(np.max(np.abs(b))),
                                              1e-30)


def phase_four_chips(cfg, devices, *, batch: int, seq: int, big_seq: int,
                     lr: float = 1e-4):
    t0 = time.perf_counter()
    n = len(devices)
    mesh = Mesh(np.array(devices).reshape(n, 1), ("data", "model"))
    opt = AdamW(lr=lr)
    batch_np = token_batch(cfg, batch, seq)

    # 1. make_train_step: hier (reduce-scatter, sharded Adam state) against
    # allreduce (replicated), one step from the same state and batch
    loss_h, p_h, mu_h = _train_step_once(cfg, mesh, "hier", opt, batch_np)
    loss_a, p_a, mu_a = _train_step_once(cfg, mesh, "allreduce", opt,
                                         batch_np)
    check(math.isfinite(loss_h) and abs(loss_h - loss_a)
          <= LOSS_RTOL * abs(loss_a), f"losses {loss_h} vs {loss_a}")
    # first Adam moment = 0.1 x clipped synced gradient: compares the sync
    mu_worst = max(jax.tree.leaves(jax.tree.map(_leaf_ratio, mu_h, mu_a)))
    # Adam's first step moves each weight by lr * (g/(|g|+eps) + wd*p), so
    # two syncs can leave it at most 2*lr apart before each is rounded
    p_worst = max(jax.tree.leaves(jax.tree.map(_update_excess, p_h, p_a)))
    print(f"[sync] hier vs allreduce train step: loss {loss_h:.6f} vs "
          f"{loss_a:.6f}; Adam mu max leaf ratio {mu_worst:.6g} (tolerance "
          f"{SYNC_TOL}); params max gap beyond bf16 rounding {p_worst:.6g} "
          f"(tolerance 2*lr = {2 * lr})", flush=True)
    check(mu_worst <= SYNC_TOL, f"hier/allreduce gradients {mu_worst}")
    check(p_worst <= 2 * lr, f"hier/allreduce params {p_worst}")
    del p_h, p_a, mu_h, mu_a
    drop_arrays("sync/train_step")

    # 2. hier_sync: shard_map ScatterReduce against all-reduce
    params = jax.jit(lambda k: registry.init(k, cfg),
                     out_shardings=NamedSharding(mesh, P()))(
                         jax.random.key(0))
    batch_d = jax.device_put(batch_np, NamedSharding(mesh, P("data")))
    loss_fn = lambda p, b: registry.loss_fn(p, cfg, b)  # noqa: E731
    out = {s: jax.jit(make_sync_grad_fn(loss_fn, mesh, s))(params, batch_d)
           for s in ("hier", "allreduce")}
    (l_h, g_h), (l_a, g_a) = out["hier"], out["allreduce"]
    _check_spread("scatter_reduce largest grad", _largest(g_h), n, False)
    ratios = jax.tree.leaves(jax.tree.map(
        lambda a, b: jnp.max(jnp.abs(a.astype(jnp.float32)
                                     - b.astype(jnp.float32)))
        / jnp.maximum(jnp.max(jnp.abs(b.astype(jnp.float32))), 1e-30),
        g_h, g_a))
    g_worst = max(float(r) for r in ratios)
    l_h, l_a = float(l_h), float(l_a)
    print(f"[sync] make_sync_grad_fn scatter_reduce vs allreduce: loss "
          f"{l_h:.6f} vs {l_a:.6f}; grads max leaf ratio {g_worst:.6g} "
          f"(tolerance {SYNC_TOL})", flush=True)
    check(abs(l_h - l_a) <= LOSS_RTOL * abs(l_a)
          and abs(l_h - loss_a) <= LOSS_RTOL * abs(loss_a),
          f"shard_map losses {l_h}, {l_a} vs train step {loss_a}")
    check(g_worst <= SYNC_TOL, f"scatter_reduce/allreduce grads {g_worst}")
    del params, batch_d, out, g_h, g_a, ratios
    drop_arrays("sync/hier_sync")

    # 3. one hier step at the long batch: fits only with sharded Adam state
    big = token_batch(cfg, batch, big_seq, seed=1)
    loss_big, _, _ = _train_step_once(cfg, mesh, "hier", opt, big)
    check(math.isfinite(loss_big), f"hier {batch}x{big_seq} loss {loss_big}")
    drop_arrays("sync/long_step")
    print(f"[sync] phase {time.perf_counter() - t0:.1f} s", flush=True)


# ---------------------------------------------------------------------------


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the four-chip gradient-sync phase")
    args = ap.parse_args(argv)

    cache_dir = enable_compile_cache()
    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU, JAX found {dev.platform!r}",
              file=sys.stderr)
        return 1
    cache = {"hits": 0, "misses": 0}

    def on_event(event, **_):
        for k in cache:
            if event == f"/jax/compilation_cache/cache_{k}":
                cache[k] += 1

    jax.monitoring.register_event_listener(on_event)
    print(f"devices: {len(devices)} x {dev.device_kind}; compile cache "
          f"{cache_dir}", flush=True)

    if args.four_chips:
        check(len(devices) >= 4, f"--four-chips needs 4 chips, "
              f"found {len(devices)}")
        phase_four_chips(OLMO, devices[:4], batch=4, seq=1024, big_seq=4096)
    else:
        phase_train(OLMO, steps=4, batch=4, seq=1024)
        drop_arrays("train")
        phase_serve(OLMO, n_requests=4, prompt_len=512, gen=16)
        drop_arrays("serve")
        # olmo-1b's flat gradient, split over 8 workers, each shard rounded
        # up to the kernel's 8192 block (as the shard generator pads)
        n_params = registry.param_count(OLMO)
        shard = -(-n_params // 8 // 8192) * 8192
        phase_aggregate(n_workers=8, shard_len=shard)
        drop_arrays("aggregate")

    print(f"compile cache: {cache['hits']} hits, {cache['misses']} misses",
          flush=True)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
