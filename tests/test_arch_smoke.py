"""Per-architecture smoke tests: a REDUCED variant of each assigned family
(2 layers, d_model<=512, <=4 experts) runs one forward/train step and one
prefill+decode step on CPU; output shapes + finiteness asserted; remat
changes no family's loss or gradients."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from test_scopes import ARCH

from repro.configs import ARCHS, reduced, reduced_batch
from repro.models import registry
from repro.optim import AdamW

B, S = 2, 32


@pytest.fixture(scope="module", params=sorted(ARCHS))
def arch(request):
    cfg = reduced(ARCHS[request.param])
    params = registry.init(jax.random.key(0), cfg)
    batch = reduced_batch(cfg, B, S)
    return cfg, params, batch


def test_train_step(arch):
    cfg, params, batch = arch
    opt = AdamW(lr=1e-3)

    @jax.jit
    def step(params, opt_state, batch):
        loss, grads = jax.value_and_grad(
            lambda p: registry.loss_fn(p, cfg, batch))(params)
        params, opt_state = opt.update(grads, opt_state, params)
        return params, opt_state, loss

    opt_state = opt.init(params)
    p1, o1, loss1 = step(params, opt_state, batch)
    p2, o2, loss2 = step(p1, o1, batch)
    assert jnp.isfinite(loss1) and jnp.isfinite(loss2)
    assert loss2 < loss1  # one step on the same batch must reduce loss
    for a, b in zip(jax.tree.leaves(params), jax.tree.leaves(p1)):
        assert a.shape == b.shape
        assert bool(jnp.all(jnp.isfinite(b.astype(jnp.float32))))


def test_prefill_decode(arch):
    cfg, params, batch = arch
    logits, cache = registry.prefill(params, cfg, batch, max_seq=S + 4)
    assert logits.shape[:2] == (B, S)
    assert logits.shape[2] >= cfg.vocab_size
    assert bool(jnp.all(jnp.isfinite(logits.astype(jnp.float32))))
    tok = jnp.argmax(logits[:, -1:], axis=-1)
    logits2, cache2 = registry.decode_step(params, cfg, cache,
                                           jnp.int32(S), tok)
    assert logits2.shape[0] == B and logits2.shape[1] == 1
    assert bool(jnp.all(jnp.isfinite(logits2.astype(jnp.float32))))


def test_decode_matches_prefill(arch):
    """Teacher-forced decode logits must match prefill logits position-wise
    (the KV-cache path is numerically consistent with the parallel path)."""
    cfg, params, batch = arch
    toks = batch["tokens"]
    full_logits, _ = registry.prefill(params, cfg, batch, max_seq=S)
    # prefill only the first half, then decode the second half token by token
    half = S // 2
    pre_batch = dict(batch, tokens=toks[:, :half])
    _, cache = registry.prefill(params, cfg, pre_batch, max_seq=S)
    for t in range(half, min(half + 3, S)):
        logits, cache = registry.decode_step(params, cfg, cache,
                                             jnp.int32(t), toks[:, t:t + 1])
        ref = full_logits[:, t]
        got = logits[:, 0]
        assert jnp.allclose(ref, got, rtol=2e-2, atol=2e-3), (
            cfg.arch_id, t, float(jnp.max(jnp.abs(ref - got))))


@pytest.mark.parametrize("family", sorted(ARCH))
def test_remat_keeps_loss_and_grads(family):
    """``remat=True`` recomputes each layer body in the backward pass
    (``jax.checkpoint``) and gives the loss and gradients of the plain
    scan."""
    cfg = reduced(ARCHS[ARCH[family]])
    params = registry.init(jax.random.key(0), cfg)
    batch = reduced_batch(cfg, B, S)
    (l0, g0), (l1, g1) = (
        jax.jit(jax.value_and_grad(
            lambda p, c=c: registry.loss_fn(p, c, batch)))(params)
        for c in (cfg.replace(remat=False), cfg.replace(remat=True)))
    np.testing.assert_allclose(float(l1), float(l0), rtol=1e-6, atol=1e-6)
    for a, b in zip(jax.tree.leaves(g1), jax.tree.leaves(g0)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-6,
                                   atol=1e-6)
