"""Per-kernel validation: shape/dtype sweeps, assert_allclose against the
pure-jnp oracles in repro.kernels.ref (interpret=True on CPU)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref

RNG = np.random.RandomState(0)


def _tol(dtype):
    return dict(rtol=2e-2, atol=2e-2) if dtype == jnp.bfloat16 \
        else dict(rtol=2e-4, atol=2e-5)


# ---------------------------------------------------------------------------
# hier_agg
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("n_workers", [1, 2, 8, 17])
@pytest.mark.parametrize("length", [128, 1000, 8192, 20000])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_aggregate_shards(n_workers, length, dtype):
    x = jnp.array(RNG.randn(n_workers, length), dtype)
    got = ops.aggregate_shards(x, block=1024)
    want = ref.ref_aggregate(x)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), **_tol(dtype))


@pytest.mark.parametrize("length", [512, 5000])
def test_aggregate_and_apply(length):
    x = jnp.array(RNG.randn(4, length), jnp.float32)
    p = jnp.array(RNG.randn(length), jnp.float32)
    got = ops.aggregate_and_apply(x, p, lr=0.05, block=512)
    want = ref.ref_aggregate_apply(x, p, 0.05)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)


# ---------------------------------------------------------------------------
# flash attention
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("seq,block", [(128, 64), (160, 64), (256, 128)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_causal(seq, block, dtype):
    b, h, d = 2, 3, 64
    q = jnp.array(RNG.randn(b, h, seq, d), dtype)
    k = jnp.array(RNG.randn(b, h, seq, d), dtype)
    v = jnp.array(RNG.randn(b, h, seq, d), dtype)
    got = ops.flash_attention(q, k, v, causal=True, block_q=block,
                              block_k=block)
    want = ref.ref_attention(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), **_tol(dtype))


@pytest.mark.parametrize("window", [16, 64, 100])
def test_flash_sliding_window(window):
    b, h, seq, d = 1, 2, 192, 32
    q = jnp.array(RNG.randn(b, h, seq, d), jnp.float32)
    k = jnp.array(RNG.randn(b, h, seq, d), jnp.float32)
    v = jnp.array(RNG.randn(b, h, seq, d), jnp.float32)
    got = ops.flash_attention(q, k, v, causal=True, window=window,
                              block_q=64, block_k=64)
    want = ref.ref_attention(q, k, v, causal=True, window=window)
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-5)


def test_flash_matches_model_blockwise():
    """The model-side jnp blockwise attention and the Pallas kernel agree."""
    from repro.models.layers import blockwise_attention
    b, h, seq, d = 2, 2, 128, 32
    q = jnp.array(RNG.randn(b, h, seq, d), jnp.float32)
    k = jnp.array(RNG.randn(b, h, seq, d), jnp.float32)
    v = jnp.array(RNG.randn(b, h, seq, d), jnp.float32)
    got = ops.flash_attention(q, k, v, causal=True, block_q=64, block_k=64)
    # model layout is (b, s, h, d)
    want = blockwise_attention(q.transpose(0, 2, 1, 3),
                               k.transpose(0, 2, 1, 3),
                               v.transpose(0, 2, 1, 3),
                               causal=True).transpose(0, 2, 1, 3)
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-5)


def _bhsd(x):
    return x.transpose(0, 2, 1, 3)


@pytest.mark.parametrize("seq,block,window", [
    (128, 128, 0),       # one block
    (256, 64, 0),        # several blocks, causal skipping
    (256, 64, 48),       # several blocks, window skipping on both sides
    (192, 64, 16)])      # window narrower than a block
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_backward_matches_blockwise_vjp(seq, block, window, dtype):
    """The interpreted forward (output and lse residual) and backward
    kernels (dq, dk, dv) against jax.vjp of the model's blockwise path."""
    from repro.kernels import flash_attention as fa
    from repro.models.layers import blockwise_attention
    b, h, d = 1, 2, 32
    q, k, v, do = (jnp.array(RNG.randn(b, h, seq, d), dtype)
                   for _ in range(4))
    got, vjp = jax.vjp(
        lambda q, k, v: ops.flash_attention(q, k, v, causal=True,
                                            window=window, block_q=block,
                                            block_k=block), q, k, v)
    want, vjp_ref = jax.vjp(
        lambda q, k, v: _bhsd(blockwise_attention(
            _bhsd(q), _bhsd(k), _bhsd(v), causal=True,
            sliding_window=window)), q, k, v)
    for a, r in zip((got, *vjp(do)), (want, *vjp_ref(do))):
        assert a.dtype == r.dtype == dtype
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(r, np.float32), **_tol(dtype))

    # the lse residual: each row's log-sum-exp of its visible scaled scores
    _, lse = fa.flash_forward(q.reshape(b * h, seq, d),
                              k.reshape(b * h, seq, d),
                              v.reshape(b * h, seq, d), causal=True,
                              window=window, block_q=block, block_k=block)
    assert lse.shape == (b * h, seq, fa.LANES) and lse.dtype == jnp.float32
    s = jnp.einsum("bhqd,bhkd->bhqk", q.astype(jnp.float32),
                   k.astype(jnp.float32)) * d ** -0.5
    pos = jnp.arange(seq)
    mask = (pos[:, None] >= pos[None, :]) & (
        pos[:, None] - pos[None, :] < (window or seq))
    lse_ref = jax.nn.logsumexp(jnp.where(mask, s, -jnp.inf), axis=-1)
    np.testing.assert_allclose(np.asarray(lse[..., 0]),
                               np.asarray(lse_ref).reshape(b * h, seq),
                               rtol=2e-5, atol=2e-5)
    np.testing.assert_array_equal(np.asarray(lse),
                                  np.asarray(lse[..., :1]).repeat(fa.LANES,
                                                                  axis=-1))
