"""Pallas TPU kernels for the framework's compute hot-spots.

 - hier_agg:        sharded gradient mean-aggregation + fused SGD apply
                    (the paper's shard-aggregator hot loop)
 - flash_attention: online-softmax causal/sliding-window attention
 - ssd_scan:        Mamba2 chunked SSD scan with VMEM-resident state

``ops`` holds the jit'd padded wrappers (differentiable where training
needs it); ``ref`` the independent pure-jnp oracles.

The platform picks the execution mode (``resolve_interpret``): compiled
Mosaic kernels on TPU, the Pallas interpreter on CPU, and an error on any
other backend. Every kernel keeps an explicit ``interpret`` argument so a
test can force a TPU compile from a CPU host.
"""
from __future__ import annotations

from typing import Optional

import jax


def resolve_interpret(interpret: Optional[bool] = None) -> bool:
    """``interpret`` if given, else the mode the default backend needs."""
    if interpret is not None:
        return interpret
    backend = jax.default_backend()
    if backend == "tpu":
        return False
    if backend == "cpu":
        return True
    raise RuntimeError(
        f"Pallas kernels here target TPU (compiled) or CPU (interpreted); "
        f"the default backend is {backend!r}")
