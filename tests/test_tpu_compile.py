"""The main path's Pallas kernels compile for a TPU v5e at real widths.

Nothing runs: the TPU compiler, installed with jax, compiles for a chip
that is described (``v5e:2x2``) and not attached, so Mosaic refusals —
unsupported primitives, unaligned tiles, VMEM overruns — show up here
instead of on the chip. The topology is described inside a module fixture,
never at import: only one process may load the TPU library at a time, and
every test worker imports this file.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import ops


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc
    # a described-chip compile is written to the persistent cache but can
    # never be read back without the chip: keep the cache out of it
    cache_was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", os.environ.get("TPU_LOG_DIR", "disabled"))
        try:
            topo = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:  # no TPU compiler on this host
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", cache_was)


def _compiled_text(fn, *shapes, one_chip):
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in shapes]
    return jax.jit(fn).lower(*args).compile().as_text()


def test_hier_agg_compiles(one_chip):
    txt = _compiled_text(
        lambda x: ops.aggregate_shards(x, interpret=False),
        ((8, 2 ** 20), jnp.float32), one_chip=one_chip)
    assert "tpu_custom_call" in txt
    assert "%hier_agg" in txt    # the kernel's stable name in a device trace


def test_flash_forward_compiles_at_olmo_widths(one_chip):
    qkv = ((1, 16, 4096, 128), jnp.bfloat16)    # olmo-1b: 16 heads x 128
    txt = _compiled_text(
        lambda q, k, v: ops.flash_attention(q, k, v, causal=True,
                                            interpret=False),
        qkv, qkv, qkv, one_chip=one_chip)
    assert "tpu_custom_call" in txt
    assert "%flash_attention" in txt


def test_ssd_scan_compiles_at_mamba2_widths(one_chip):
    # mamba2-2.7b: d_inner 5120 = 80 heads x 64, state 128, chunk 256
    b, s, h, p, n = 1, 4096, 80, 64, 128
    txt = _compiled_text(
        lambda x, dt, A, B, C, D: ops.ssd_scan(x, dt, A, B, C, D, chunk=256,
                                               interpret=False),
        ((b, s, h, p), jnp.bfloat16), ((b, s, h), jnp.float32),
        ((h,), jnp.float32), ((b, s, n), jnp.bfloat16),
        ((b, s, n), jnp.bfloat16), ((h,), jnp.float32), one_chip=one_chip)
    assert "tpu_custom_call" in txt
    assert "%ssd_scan" in txt
