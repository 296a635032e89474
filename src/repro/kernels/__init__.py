"""Pallas TPU kernels for the framework's compute hot-spots.

 - hier_agg:        sharded gradient mean-aggregation + fused SGD apply
                    (the paper's shard-aggregator hot loop)
 - flash_attention: online-softmax causal/sliding-window attention
 - ssd:             Mamba-2's chunked SSD core, forward and backward

``ops`` holds the jit'd padded wrappers (differentiable where training
needs it); ``ref`` the independent pure-jnp oracles.

The platform picks the execution mode (``resolve_interpret``): compiled
Mosaic kernels on TPU, the Pallas interpreter on CPU, and an error on any
other backend. Every kernel keeps an explicit ``interpret`` argument so a
test can force a TPU compile from a CPU host.

Whether the model takes a kernel is decided here too, from what the code
observes (``flash_attention_applies``, ``ssd_kernel_applies``): the
platform, the device count and the shapes, with no option to turn it on or
off.
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


def pallas_compiles() -> bool:
    """Whether the default backend compiles Pallas kernels (the TPU)."""
    return jax.default_backend() == "tpu"


def flash_attention_applies(seq: int, *, causal: bool,
                            self_attention: bool) -> bool:
    """Whether attention over ``seq`` query tokens takes the Pallas flash
    kernels: where they compile, on one device (Mosaic kernels cannot be
    partitioned across devices automatically), for causal self-attention
    with no cache (training and cache-free forwards), over a sequence that
    tiles into 128-row blocks. Everything else -- decode and prefill into a
    cache, cross-attention, the non-causal encoder, other lengths, a step
    over several devices, any CPU run -- takes
    ``models.layers.blockwise_attention``."""
    return (pallas_compiles() and jax.device_count() == 1
            and self_attention and causal and seq > 1 and seq % 128 == 0)


def ssd_kernel_applies(chunk: int, heads: int, head_dim: int) -> bool:
    """Whether Mamba-2's SSD core takes the Pallas kernels: where they
    compile, on one device, for a chunk that tiles into 128 rows and heads
    that split into blocks of a multiple of 128 lanes
    (``kernels.ssd.head_block``). Everything else -- other shapes, a step
    over several devices, any CPU run -- takes
    ``models.mamba2.ssd_chunked``."""
    from repro.kernels.ssd import head_block
    return (pallas_compiles() and jax.device_count() == 1
            and chunk % 128 == 0 and head_block(heads, head_dim) is not None)
