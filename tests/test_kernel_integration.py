"""Kernel-in-model integration: taking the Pallas paths must not change
model outputs or gradients.

Flash attention has no switch: ``repro.kernels.flash_attention_applies``
takes it where Pallas compiles (the TPU). These tests make the CPU look
like such a platform (``flash_forced``), so the kernels run interpreted,
and compare with the blockwise path the CPU takes on its own."""
import jax
import numpy as np
import pytest

from repro import kernels, obs
from repro.configs import ARCHS, reduced, reduced_batch
from repro.models import registry


@pytest.fixture
def flash_forced(monkeypatch):
    """Within the test, call ``flash(fn)`` to run ``fn`` as if on the TPU."""
    def flash(fn):
        with monkeypatch.context() as mp:
            mp.setattr(kernels, "pallas_compiles", lambda: True)
            return fn()
    return flash


def _counts():
    c = obs.snapshot()["counters"]
    return c.get("attn.flash", 0), c.get("attn.blockwise", 0)


def _assert_grads_close(g0, g1):
    for a, b in zip(jax.tree.leaves(g0), jax.tree.leaves(g1)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=5e-4, atol=1e-5)


@pytest.mark.parametrize("arch", ["olmo-1b", "qwen2.5-3b"])
def test_flash_kernel_path_matches(arch, flash_forced):
    """Loss and gradients through the flash kernels equal the blockwise
    path's; qwen2.5-3b has grouped K/V heads (GQA)."""
    cfg = reduced(ARCHS[arch])
    params = registry.init(jax.random.key(0), cfg)
    batch = reduced_batch(cfg, 2, 128)
    loss = jax.value_and_grad(lambda p: registry.loss_fn(p, cfg, batch))
    base, g0 = loss(params)
    flash, g1 = flash_forced(lambda: loss(params))
    np.testing.assert_allclose(float(base), float(flash), rtol=1e-5)
    _assert_grads_close(g0, g1)


def test_flash_kernel_grads_match(flash_forced):
    """Several 128-row blocks with remat on, as the train step runs."""
    cfg = reduced(ARCHS["olmo-1b"]).replace(remat=True)
    params = registry.init(jax.random.key(1), cfg)
    batch = reduced_batch(cfg, 1, 384)
    grad = jax.grad(lambda p: registry.loss_fn(p, cfg, batch))
    _assert_grads_close(grad(params), flash_forced(lambda: grad(params)))


def test_hybrid_window_kernel_matches(flash_forced):
    """Sliding-window flash path == windowed blockwise in the hybrid:
    zamba2's window (32 here) skips whole tiles. Loss and gradients."""
    cfg = reduced(ARCHS["zamba2-7b"])
    params = registry.init(jax.random.key(0), cfg)
    batch = reduced_batch(cfg, 1, 384)       # three 128-row blocks
    loss = jax.value_and_grad(lambda p: registry.loss_fn(p, cfg, batch))
    base, g0 = loss(params)
    flash, g1 = flash_forced(lambda: loss(params))
    np.testing.assert_allclose(float(base), float(flash), rtol=1e-4)
    _assert_grads_close(g0, g1)


@pytest.mark.parametrize("seq,flash", [(128, True), (64, False),
                                       (200, False)])
def test_attention_counters_read_dispatch(seq, flash, flash_forced):
    """``attn.flash`` and ``attn.blockwise`` count, at trace time, what the
    dispatch chose: flash for a length that tiles into 128-row blocks,
    blockwise for other lengths and for decode against a cache."""
    cfg = reduced(ARCHS["olmo-1b"])
    params = registry.init(jax.random.key(0), cfg)
    batch = reduced_batch(cfg, 1, seq)
    before = _counts()
    flash_forced(lambda: jax.jit(
        lambda p: registry.loss_fn(p, cfg, batch)).lower(params))
    f, b = (n - m for n, m in zip(_counts(), before))
    # the layer scan traces its attention once
    assert (f, b) == ((1, 0) if flash else (0, 1))

    # decode writes a cache: blockwise even where the kernels compile
    cache = registry.init_decode_cache(params, cfg, 1, 256)
    tokens = batch["tokens"][:, :1]
    before = _counts()
    flash_forced(lambda: jax.jit(
        lambda p, c: registry.decode_step(p, cfg, c, seq, tokens)).lower(
            params, cache))
    f, b = (n - m for n, m in zip(_counts(), before))
    assert f == 0 and b >= 1


def test_cpu_takes_blockwise(monkeypatch):
    """Without a platform that compiles Pallas, nothing takes the kernels;
    where it compiles, only a causal, cache-free self-attention over a
    multiple of 128 tokens on one device does."""
    applies = kernels.flash_attention_applies
    assert not applies(2048, causal=True, self_attention=True)
    monkeypatch.setattr(kernels, "pallas_compiles", lambda: True)
    assert applies(2048, causal=True, self_attention=True)
    assert not applies(2048, causal=False, self_attention=True)
    assert not applies(2048, causal=True, self_attention=False)
    assert not applies(2000, causal=True, self_attention=True)
    assert not applies(1, causal=True, self_attention=True)
    monkeypatch.setattr(jax, "device_count", lambda: 4)
    assert not applies(2048, causal=True, self_attention=True)


def _ssd_counts():
    c = obs.snapshot()["counters"]
    return c.get("ssd.kernel", 0), c.get("ssd.chunked", 0)


@pytest.mark.parametrize("arch", ["mamba2-2.7b", "zamba2-7b"])
def test_ssd_kernel_path_matches(arch, flash_forced):
    """Loss and gradients through the SSD kernels equal ``ssd_chunked``'s:
    chunks of 128 over 200 tokens (a padded last chunk), two head blocks
    of 8 heads of 16; zamba2's hybrid calls the same Mamba block."""
    cfg = reduced(ARCHS[arch]).replace(ssm_chunk=128, remat=True)
    params = registry.init(jax.random.key(0), cfg)
    batch = reduced_batch(cfg, 1, 200)
    loss = jax.value_and_grad(lambda p: registry.loss_fn(p, cfg, batch))
    base, g0 = loss(params)
    before = _ssd_counts()
    kern, g1 = flash_forced(lambda: loss(params))
    assert _ssd_counts()[0] > before[0]
    np.testing.assert_allclose(float(base), float(kern), rtol=1e-5)
    _assert_grads_close(g0, g1)


@pytest.mark.parametrize("chunk,kernel", [(128, True), (16, False)])
def test_ssd_counters_read_dispatch(chunk, kernel, flash_forced):
    """``ssd.kernel`` and ``ssd.chunked`` count, at trace time, the path a
    build took: the kernels for chunks of 128 rows, ``ssd_chunked`` for
    chunks that do not tile. The layer scan traces its block once."""
    cfg = reduced(ARCHS["mamba2-2.7b"]).replace(ssm_chunk=chunk)
    pshapes = jax.eval_shape(lambda k: registry.init(k, cfg),
                             jax.random.key(0))
    batch = jax.eval_shape(lambda: reduced_batch(cfg, 1, 256))
    before = _ssd_counts()
    flash_forced(lambda: jax.make_jaxpr(
        lambda p, b: registry.loss_fn(p, cfg, b))(pshapes, batch))
    k, c = (n - m for n, m in zip(_ssd_counts(), before))
    assert (k, c) == ((1, 0) if kernel else (0, 1))


def test_cpu_takes_ssd_chunked(monkeypatch):
    """Without a platform that compiles Pallas, no SSD core takes the
    kernels; where it compiles, one device with chunks of a multiple of
    128 rows and heads that fill 128-lane blocks does."""
    applies = kernels.ssd_kernel_applies
    assert not applies(256, 80, 64)
    monkeypatch.setattr(kernels, "pallas_compiles", lambda: True)
    assert applies(256, 80, 64)           # mamba2-2.7b: 10 blocks of 8
    assert applies(256, 112, 64)          # zamba2-7b: 14 blocks of 8
    assert applies(128, 16, 16)
    assert not applies(200, 80, 64)       # chunk does not tile
    assert not applies(64, 80, 64)
    assert not applies(256, 3, 48)        # 144 lanes: no 128-lane block
    assert not applies(256, 12, 16)       # 8 heads do not divide 12
    monkeypatch.setattr(jax, "device_count", lambda: 4)
    assert not applies(256, 80, 64)
