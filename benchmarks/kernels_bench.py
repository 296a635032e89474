"""Kernel micro-bench: us_per_call of each Pallas kernel (the platform picks
the mode, so on CPU this times the Pallas interpreter — CPU wall times are
NOT TPU times; the roofline in benchmarks/roofline.py is
the performance source of truth. This bench proves the kernels execute and
tracks relative regressions)."""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.common import timed
from repro.kernels import ops

RNG = np.random.RandomState(0)


def run() -> list:
    rows = []
    # hier_agg: 16 workers x 1M-element shard
    sh = jnp.array(RNG.randn(16, 1 << 20), jnp.float32)
    _, us = timed(lambda: jax.block_until_ready(
        ops.aggregate_shards(sh, block=8192)), reps=3)
    rows.append({"kernel": "hier_agg", "shape": "16x1Mi", "us_per_call": us})

    q = jnp.array(RNG.randn(1, 4, 1024, 64), jnp.float32)
    _, us = timed(lambda: jax.block_until_ready(
        ops.flash_attention(q, q, q, causal=True, block_q=256, block_k=256)),
        reps=2)
    rows.append({"kernel": "flash_attention", "shape": "b1h4s1024d64",
                 "us_per_call": us})

    b, s, h, p, n = 1, 512, 8, 64, 32
    x = jnp.array(RNG.randn(b, s, h, p), jnp.float32)
    dt = jnp.array(np.abs(RNG.randn(b, s, h)) * 0.5, jnp.float32)
    A = -jnp.ones(h, jnp.float32)
    B = jnp.array(RNG.randn(b, s, n), jnp.float32)
    C = jnp.array(RNG.randn(b, s, n), jnp.float32)
    D = jnp.ones(h, jnp.float32)
    _, us = timed(lambda: jax.block_until_ready(
        ops.ssd_scan(x, dt, A, B, C, D, chunk=128)[0]), reps=2)
    rows.append({"kernel": "ssd_scan", "shape": "b1s512h8p64n32",
                 "us_per_call": us})
    return rows


def summarize(rows) -> str:
    return "; ".join(f"{r['kernel']}={r['us_per_call']:.0f}us" for r in rows)


if __name__ == "__main__":
    for r in run():
        print(r)
