"""Model-layer numerics: property-based checks of the blockwise/chunked
forms against naive references, vocab-padding handling, rope invariants."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
try:
    from hypothesis import example, given, settings
    from hypothesis import strategies as st
except ImportError:                      # optional dep: fixed example cases
    from hypothesis_fallback import example, given, settings, st

from repro.kernels import ops, ref
from repro.models import layers as L
from repro.models.base import ModelConfig
from repro.models.mamba2 import ssd_chunked


@given(seq=st.integers(4, 96), qb=st.sampled_from([4, 16, 64]),
       window=st.sampled_from([0, 8, 32]))
@settings(max_examples=20, deadline=None)
def test_blockwise_attention_property(seq, qb, window):
    rng = np.random.RandomState(seq * 7 + qb)
    b, h, d = 1, 2, 16
    q = jnp.array(rng.randn(b, seq, h, d), jnp.float32)
    k = jnp.array(rng.randn(b, seq, h, d), jnp.float32)
    v = jnp.array(rng.randn(b, seq, h, d), jnp.float32)
    got = L.blockwise_attention(q, k, v, causal=True, sliding_window=window,
                                q_block=qb)
    want = ref.ref_attention(q.transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3),
                             v.transpose(0, 2, 1, 3), causal=True,
                             window=window).transpose(0, 2, 1, 3)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-4, atol=2e-5)


def _ssd_inputs(rng, b, s, h, p, n, dt_floor=0.0):
    x = jnp.array(rng.randn(b, s, h, p), jnp.float32)
    dt = jnp.array(np.abs(rng.randn(b, s, h)) * 0.4 + dt_floor, jnp.float32)
    A = -jnp.array(np.abs(rng.randn(h)) + 0.3, jnp.float32)
    B = jnp.array(rng.randn(b, s, n), jnp.float32)
    C = jnp.array(rng.randn(b, s, n), jnp.float32)
    return x, dt, A, B, C


def _ssd_grads(fns, rng, args, y_shape, S_shape):
    """Gradients of each ``fn``'s (y, final_state) with respect to all its
    inputs, through one scalar with the same random weights on both
    outputs: a full vector-Jacobian product."""
    wy = jnp.array(rng.randn(*y_shape), jnp.float32)
    wS = jnp.array(rng.randn(*S_shape), jnp.float32)

    def grads(f):
        def loss(*a):
            y, S = f(*a)
            return jnp.sum(y * wy) + jnp.sum(S * wS)
        return jax.jit(jax.grad(loss, argnums=tuple(range(len(args)))))(
            *args)
    return [grads(f) for f in fns]


def _assert_grads_close(got, want, rtol, atol):
    for name, g, w in zip(("x", "dt", "A", "B", "C", "D", "initial_state"),
                          got, want):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w), rtol=rtol,
                                   atol=atol * float(jnp.max(jnp.abs(w))),
                                   err_msg=f"d/d{name}")


@given(s=st.integers(8, 80), chunk=st.sampled_from([8, 16, 32]))
@example(s=45, chunk=16)                 # a padded last chunk
@settings(max_examples=15, deadline=None)
def test_ssd_chunked_equals_sequential(s, chunk):
    """Outputs, final state and the gradient with respect to every input
    match the per-token recurrence."""
    rng = np.random.RandomState(s * 13 + chunk)
    b, h, p, n = 1, 2, 8, 4
    x, dt, A, B, C = _ssd_inputs(rng, b, s, h, p, n, dt_floor=0.01)
    D = jnp.array(rng.randn(h), jnp.float32)
    y, S = jax.jit(ssd_chunked, static_argnums=6)(x, dt, A, B, C, D, chunk)
    yr, Sr = ref.ref_ssd(x, dt, A, B, C, D)
    np.testing.assert_allclose(np.asarray(y), np.asarray(yr),
                               rtol=3e-4, atol=3e-4)
    np.testing.assert_allclose(np.asarray(S), np.asarray(Sr),
                               rtol=3e-4, atol=3e-4)

    got, want = _ssd_grads(
        [lambda *a: ssd_chunked(*a, chunk), ref.ref_ssd], rng,
        (x, dt, A, B, C, D), y.shape, S.shape)
    _assert_grads_close(got, want, rtol=1e-4, atol=1e-5)


@pytest.mark.parametrize("s,chunk", [(64, 16), (100, 32), (256, 64)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_ssd_chunked_matches_sequential_by_dtype(s, chunk, dtype):
    """Outputs and final state against the per-token recurrence with
    float32 and bfloat16 inputs, including a padded last chunk (100)."""
    rng = np.random.RandomState(0)
    b, h, p, n = 2, 4, 16, 8
    x = jnp.array(rng.randn(b, s, h, p), dtype)
    dt = jnp.array(np.abs(rng.randn(b, s, h)) * 0.5 + 0.01, dtype)
    A = -jnp.array(np.abs(rng.randn(h)) + 0.5, jnp.float32)
    B = jnp.array(rng.randn(b, s, n), dtype)
    C = jnp.array(rng.randn(b, s, n), dtype)
    D = jnp.array(rng.randn(h), jnp.float32)
    y, S = jax.jit(ssd_chunked, static_argnums=6)(x, dt, A, B, C, D, chunk)
    yr, Sr = ref.ref_ssd(x, dt, A, B, C, D)
    bf16 = dtype == jnp.bfloat16
    tol = 5e-2 if bf16 else 2e-4
    np.testing.assert_allclose(np.asarray(y, np.float32),
                               np.asarray(yr, np.float32), rtol=tol, atol=tol)
    tol = 1e-2 if bf16 else 2e-4
    np.testing.assert_allclose(np.asarray(S), np.asarray(Sr), rtol=tol,
                               atol=tol)


def test_ssd_state_continuation():
    """Splitting a sequence and carrying the state == processing it whole,
    in the outputs and in the gradients, which flow back through the
    carried state (``initial_state``) into the first half."""
    rng = np.random.RandomState(0)
    b, s, h, p, n = 1, 64, 2, 8, 4
    x, dt, A, B, C = _ssd_inputs(rng, b, s, h, p, n)
    D = jnp.zeros(h, jnp.float32)
    h1 = 32

    def whole(x, dt, A, B, C, D):
        return ssd_chunked(x, dt, A, B, C, D, 16)

    def halves(x, dt, A, B, C, D):
        y1, S1 = ssd_chunked(x[:, :h1], dt[:, :h1], A, B[:, :h1], C[:, :h1],
                             D, 16)
        y2, S2 = ssd_chunked(x[:, h1:], dt[:, h1:], A, B[:, h1:], C[:, h1:],
                             D, 16, initial_state=S1)
        return jnp.concatenate([y1, y2], 1), S2

    y_full, S_full = jax.jit(whole)(x, dt, A, B, C, D)
    y_split, S2 = jax.jit(halves)(x, dt, A, B, C, D)
    np.testing.assert_allclose(np.asarray(y_split),
                               np.asarray(y_full), rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(np.asarray(S2), np.asarray(S_full),
                               rtol=1e-4, atol=1e-4)

    got, want = _ssd_grads([halves, whole], rng, (x, dt, A, B, C, D),
                           y_full.shape, S_full.shape)
    _assert_grads_close(got, want, rtol=1e-4, atol=1e-5)


@pytest.mark.parametrize("b,s,h,p,n,chunk,init", [
    (1, 64, 4, 32, 64, 32, False),        # state 64, whole chunks
    (2, 64, 2, 64, 128, 32, False),       # state 128
    (1, 45, 2, 64, 64, 16, False),        # a padded last chunk
    (1, 48, 4, 32, 64, 16, True),         # a carried-in state
    (2, 40, 16, 64, 128, 16, True),       # two head blocks of 8, all of it
])
def test_ssd_kernel_matches_chunked_and_sequential(b, s, h, p, n, chunk,
                                                   init):
    """The Pallas SSD kernels (interpreted) against ``ssd_chunked`` and
    the per-token recurrence: y, the final state, and the gradient with
    respect to every input, the carried-in state included."""
    rng = np.random.RandomState(s * 7 + n + h)
    x, dt, A, B, C = _ssd_inputs(rng, b, s, h, p, n, dt_floor=0.01)
    D = jnp.array(rng.randn(h), jnp.float32)
    S0 = jnp.array(rng.randn(b, h, n, p) if init else np.zeros((b, h, n, p)),
                   jnp.float32)
    args = (x, dt, A, B, C, D, S0)

    def kernel(x, dt, A, B, C, D, S0):
        return ops.ssd(x, dt, A, B, C, D, chunk=chunk, initial_state=S0,
                       interpret=True)

    def chunked(x, dt, A, B, C, D, S0):
        return ssd_chunked(x, dt, A, B, C, D, chunk, initial_state=S0)

    y, S = jax.jit(kernel)(*args)
    for yw, Sw in (jax.jit(chunked)(*args), ref.ref_ssd(*args)):
        np.testing.assert_allclose(np.asarray(y), np.asarray(yw),
                                   rtol=3e-4, atol=3e-4)
        np.testing.assert_allclose(np.asarray(S), np.asarray(Sw),
                                   rtol=3e-4, atol=3e-4)

    got, *want = _ssd_grads([kernel, chunked, ref.ref_ssd], rng, args,
                            y.shape, S.shape)
    for w in want:
        _assert_grads_close(got, w, rtol=1e-4, atol=1e-5)


def test_cross_entropy_ignores_vocab_padding():
    cfg = ModelConfig(vocab_size=500)
    rng = np.random.RandomState(0)
    logits_core = jnp.array(rng.randn(2, 8, 500), jnp.float32)
    # padded columns filled with huge values must not change the loss
    pad = jnp.full((2, 8, cfg.vocab_padded - 500), 50.0)
    logits_padded = jnp.concatenate([logits_core, pad], axis=-1)
    labels = jnp.array(rng.randint(0, 500, (2, 8)), jnp.int32)
    a = L.cross_entropy(logits_padded, labels, cfg)
    cfg_exact = ModelConfig(vocab_size=500)
    b = L.cross_entropy(
        jnp.concatenate([logits_core,
                         jnp.full((2, 8, cfg.vocab_padded - 500), -1e30)],
                        axis=-1), labels, cfg_exact)
    np.testing.assert_allclose(float(a), float(b), rtol=1e-6)


def test_cross_entropy_masks_negative_labels():
    cfg = ModelConfig(vocab_size=100)
    logits = jnp.zeros((1, 4, cfg.vocab_padded))
    labels = jnp.array([[5, -1, -1, 7]], jnp.int32)
    loss = L.cross_entropy(logits, labels, cfg)
    want = np.log(100.0)  # uniform over true vocab
    np.testing.assert_allclose(float(loss), want, rtol=1e-5)


def test_rope_relative_position_invariance():
    """<rope(q,i), rope(k,j)> depends only on i-j."""
    rng = np.random.RandomState(0)
    q = jnp.array(rng.randn(1, 1, 1, 32), jnp.float32)
    k = jnp.array(rng.randn(1, 1, 1, 32), jnp.float32)

    def dot_at(i, j):
        qi = L.apply_rope(q, jnp.array([i]), 10_000.0)
        kj = L.apply_rope(k, jnp.array([j]), 10_000.0)
        return float(jnp.sum(qi * kj))

    np.testing.assert_allclose(dot_at(5, 3), dot_at(105, 103), rtol=1e-4)
    np.testing.assert_allclose(dot_at(0, 0), dot_at(77, 77), rtol=1e-4)


def test_causal_conv_state_continuation():
    from repro.models.mamba2 import causal_conv
    rng = np.random.RandomState(1)
    x = jnp.array(rng.randn(2, 20, 6), jnp.float32)
    w = jnp.array(rng.randn(4, 6), jnp.float32)
    bias = jnp.array(rng.randn(6), jnp.float32)
    y_full, st_full = causal_conv(x, w, bias)
    y1, st1 = causal_conv(x[:, :11], w, bias)
    y2, st2 = causal_conv(x[:, 11:], w, bias, state=st1)
    np.testing.assert_allclose(np.asarray(jnp.concatenate([y1, y2], 1)),
                               np.asarray(y_full), rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(np.asarray(st2), np.asarray(st_full),
                               rtol=1e-5)
