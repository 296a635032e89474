"""Training launcher: real training on the available devices, with the
batch split over the ``data`` axis of a (n_devices, 1) mesh:

    PYTHONPATH=src python -m repro.launch.train --arch olmo-1b --reduced \
        --steps 50 --batch 8 --seq 128

The production-mesh lowering check is ``python -m repro.launch.dryrun``.
The SMLT strategy knob (--strategy hier|hier1|allreduce) selects the
gradient-synchronization dataflow (see launch/steps.py).
"""
from __future__ import annotations

import argparse
import time

import jax
import numpy as np
from jax.sharding import Mesh

from repro.configs import ARCHS, reduced
from repro.data import DataConfig, ShardedLoader, TokenDataset
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.steps import make_train_step
from repro.models import registry
from repro.optim import AdamW, warmup_cosine


def make_local_mesh():
    devs = np.array(jax.devices())
    return Mesh(devs.reshape(len(devs), 1), ("data", "model"))


def init_state(cfg, opt, pshard, oshard):
    """Params (seed 0) and optimizer state built in place, in their step
    layouts: a sharded optimizer state never exists replicated."""
    params = jax.jit(lambda k: registry.init(k, cfg),
                     out_shardings=pshard)(jax.random.key(0))
    return params, jax.jit(opt.init, out_shardings=oshard)(params)


def train(cfg, *, steps: int, batch: int, seq: int, strategy: str,
          lr: float = 3e-4, log_every: int = 10, loader=None):
    """-> (params, losses, step_s): ``step_s[i]`` is step i's host wall
    time, data loading and (for the first step) compilation included."""
    mesh = make_local_mesh()
    opt = AdamW(lr=lr, schedule=warmup_cosine(max(steps // 20, 1), steps))
    step_fn, pshard, oshard, bshard_fn = make_train_step(
        cfg, mesh, strategy=strategy, optimizer=opt)
    params, opt_state = init_state(cfg, opt, pshard, oshard)

    loader = loader or ShardedLoader(TokenDataset(DataConfig(
        vocab_size=cfg.vocab_size, seq_len=seq)))
    losses, step_s = [], []
    for i in range(steps):
        t0 = time.perf_counter()
        batch_np = loader.next_batch(batch)
        b = {"tokens": batch_np["tokens"], "labels": batch_np["labels"]}
        if cfg.family == "vlm":
            b["image_embeds"] = np.zeros(
                (batch, cfg.n_image_tokens, cfg.d_vision), cfg.dtype)
        if cfg.family == "audio":
            b["audio_frames"] = np.zeros(
                (batch, cfg.n_audio_frames, cfg.d_audio), cfg.dtype)
        b = jax.device_put(b, bshard_fn(b))
        params, opt_state, loss = step_fn(params, opt_state, b)
        losses.append(float(loss))
        step_s.append(time.perf_counter() - t0)
        if i % log_every == 0 or i == steps - 1:
            print(f"step {i:5d}  loss {losses[-1]:.4f}  "
                  f"{batch * seq / step_s[-1]:,.0f} tok/s", flush=True)
    return params, losses, step_s


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True, choices=sorted(ARCHS))
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--strategy", default="hier",
                    choices=["hier", "hier1", "allreduce"])
    args = ap.parse_args()

    enable_compile_cache()
    cfg = ARCHS[args.arch]
    if args.reduced:
        cfg = reduced(cfg)
    _, losses, _ = train(cfg, steps=args.steps, batch=args.batch,
                         seq=args.seq, strategy=args.strategy, lr=args.lr)
    print(f"final loss {losses[-1]:.4f} (from {losses[0]:.4f})")


if __name__ == "__main__":
    main()
