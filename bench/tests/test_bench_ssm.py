"""The Mamba-2 cell's plain reference (bench/reference/ssm.py) and counts
(bench/counts/ssm.py) against the program, on the CPU at a size that
crosses SSD chunks: 2 layers, state 16, heads of 16, chunks of 16, 64-token
sequences, float32.

The reference makes the program's weights from the seed by its own recipe;
its quadratic SSD equals the token-by-token recurrence of
``repro.kernels.ref``; the program's loss, each leaf's gradient and three
AdamW steps agree with it to float32 round-off; and its float8 control and
its ``no_carry`` fault fail the committed limits.
"""
from __future__ import annotations

import copy

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import correct, harness, program

CELL = "train.mamba2-2.7b"
REF = harness.load_module("reference", "ssm")
COUNTS = harness.load_module("counts", "ssm")
SMALL = dict(n_layers=2, d_model=128, vocab_size=997, ssm_state=16,
             ssm_headdim=16, ssm_chunk=16, dtype="float32", remat=False)
SEQ = 64
# float32 on the CPU: both sides take full float32 products and differ by
# the order of their sums (chunked scan against the quadratic form)
F32_GAP = 1e-5


def small_cell(seed: int = 2 ** 33 + 11) -> harness.Cell:
    cell = harness.load_cell(CELL, seed=seed, seconds=1.0, trace=False)
    cell.config = copy.deepcopy(cell.config)
    cell.config["model"].update(SMALL)
    cell.traffic = dict(cell.traffic, seq_len=SEQ)
    return cell


@pytest.fixture(scope="module")
def first_steps():
    """The program's first three steps through the cell's driver, and the
    reference's on the same batches."""
    cell = small_cell()
    run = harness.load_module("drivers", "train").Run(cell, jax.devices()[:1])
    run.setup()
    prog = run.first
    run.free()
    want = REF.train(cell.config, cell.seeds()["weights"], prog["batches"],
                     jax.devices()[:1])
    return cell, prog, want


def _ref_weights(cell):
    D = REF.dims(cell.config)
    key = jax.random.key(cell.seeds()["weights"])
    return D, key, REF.top_weights(key, D), [
        REF.layer_weights(key, i, D) for i in range(D.layers)]


def _leaf(tree, name: str):
    for part in name.split("/"):
        tree = tree[part]
    return tree


def test_reference_weights_are_the_programs():
    from repro.models import registry
    cell = small_cell()
    cfg = program.model_config(cell.config)
    D, key, top, layers = _ref_weights(cell)
    prog = registry.init(key, cfg)
    names = {program.leaf_name(p) for p, _ in
             jax.tree_util.tree_flatten_with_path(prog)[0]}
    assert names == set(top) | {"blocks/" + k for k in layers[0]}
    for k, v in top.items():
        np.testing.assert_array_equal(np.asarray(_leaf(prog, k)),
                                      np.asarray(v), err_msg=k)
    for i, w in enumerate(layers):
        for k, v in w.items():
            np.testing.assert_array_equal(
                np.asarray(_leaf(prog["blocks"], k)[i]), np.asarray(v),
                err_msg=k)


def _ssd_inputs(b=2, s=SEQ, h=8, p=16, n=16, seed=0):
    ks = jax.random.split(jax.random.key(seed), 5)
    x = jax.random.normal(ks[0], (b, s, h, p))
    # dt over the published init's range, A over its [-16, -1]
    dt = jnp.exp(jax.random.uniform(ks[1], (b, s, h), minval=np.log(1e-3),
                                    maxval=np.log(1e-1)))
    A = -jax.random.uniform(ks[2], (h,), minval=1.0, maxval=16.0)
    B = jax.random.normal(ks[3], (b, s, n))
    C = jax.random.normal(ks[4], (b, s, n))
    return x, dt, A, B, C, jnp.ones((h,))


def _ref_dims(chunk=16, heads=8, no_carry=False):
    cfg = copy.deepcopy(small_cell().config)
    cfg["model"].update(ssm_chunk=chunk, ssm_headdim=cfg["model"]["d_model"]
                        * 2 // heads)
    return REF.dims(cfg, no_carry=no_carry)


def test_reference_ssd_equals_the_token_recurrence():
    from repro.kernels.ref import ref_ssd
    x, dt, A, B, C, Dk = _ssd_inputs()
    D = _ref_dims()
    assert D.head_block == 8
    y = REF.ssd(x, dt, A, B, C, D) + Dk[:, None] * x
    want, _ = ref_ssd(x, dt, A, B, C, Dk)
    np.testing.assert_allclose(np.asarray(y), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


def test_no_carry_restarts_the_recurrence_at_each_chunk():
    from repro.kernels.ref import ref_ssd
    x, dt, A, B, C, Dk = _ssd_inputs()
    D = _ref_dims(chunk=16, no_carry=True)
    y = REF.ssd(x, dt, A, B, C, D) + Dk[:, None] * x
    want = jnp.concatenate(
        [ref_ssd(x[:, i:i + 16], dt[:, i:i + 16], A, B[:, i:i + 16],
                 C[:, i:i + 16], Dk)[0] for i in range(0, SEQ, 16)], axis=1)
    np.testing.assert_allclose(np.asarray(y), np.asarray(want),
                               rtol=2e-5, atol=2e-5)
    carried = REF.ssd(x, dt, A, B, C, _ref_dims()) + Dk[:, None] * x
    assert float(jnp.max(jnp.abs(carried - y))) > 1e-2


def test_program_loss_and_gradients_match_the_reference():
    from repro.data import DataConfig, TokenDataset
    from repro.models import registry
    cell = small_cell()
    cfg = program.model_config(cell.config)
    D, key, top, layers = _ref_weights(cell)
    params = registry.init(key, cfg)
    tokens = TokenDataset(DataConfig(vocab_size=cfg.vocab_size, seq_len=SEQ,
                                     seed=3)).sample(0, 0, 2, SEQ)
    batch = {"tokens": jnp.asarray(tokens), "labels": jnp.asarray(tokens)}
    loss, grads = jax.value_and_grad(
        lambda p: registry.loss_fn(p, cfg, batch))(params)
    rep, _ = REF.dense._mesh(jax.devices()[:1])
    lval, g_top, g_layers = REF._grads(REF._programs(D, rep), top, layers,
                                       jnp.asarray(tokens))
    assert abs(float(loss) - float(lval)) < F32_GAP
    want = dict(g_top)
    for k in g_layers[0]:
        want["blocks/" + k] = jnp.stack([g[k] for g in g_layers])
    got = {program.leaf_name(p): g for p, g in
           jax.tree_util.tree_flatten_with_path(grads)[0]}
    assert set(got) == set(want)
    for k, g in got.items():
        scale = float(jnp.max(jnp.abs(want[k])))
        np.testing.assert_allclose(np.asarray(g), np.asarray(want[k]),
                                   rtol=1e-3, atol=1e-4 * scale, err_msg=k)


def test_three_adamw_steps_match_the_reference(first_steps):
    _, prog, want = first_steps
    np.testing.assert_allclose(prog["losses"], want["losses"], atol=F32_GAP)
    for key in ("grad_norms", "update_norms"):
        assert set(prog[key]) == set(want[key])
        for k, v in want[key].items():
            assert prog[key][k] == pytest.approx(v, rel=1e-4, abs=1e-9), k
    nums = correct.train_numbers(prog, want)
    assert max(nums.values()) < F32_GAP, nums


@pytest.mark.parametrize("mode,fault", [("fp8", None), ("f32", "no_carry")])
def test_control_and_no_carry_fail_the_committed_limits(first_steps, mode,
                                                        fault):
    cell, prog, want = first_steps
    got = REF.train(cell.config, cell.seeds()["weights"], prog["batches"],
                    jax.devices()[:1], mode=mode, fault=fault)
    checks = correct.train_checks(got, want, cell.limits)
    assert not all(c.ok for c in checks), checks


def test_params_match_the_programs():
    from repro.models import registry
    config = harness.load_json(harness.BENCH_DIR / "configs"
                               / "mamba2-2.7b.json")
    cfg = program.model_config(config)
    assert COUNTS.params(config) == registry.param_count(cfg)
    shapes = jax.eval_shape(lambda k: registry.init(k, cfg), jax.random.key(0))
    mats = ("wz", "wx", "wB", "wC", "wdt", "wo")
    assert COUNTS.block_matmul_params(config) == sum(
        int(np.prod(shapes["blocks"][k].shape)) for k in mats)


def test_flops_match_a_hand_count():
    config = {"model": dict(d_model=4, ssm_expand=2, ssm_headdim=4,
                            ssm_state=2, ssm_chunk=4, ssm_conv_width=4,
                            vocab_size=100, n_layers=1)}
    # d_inner 8, 2 heads of 4, vocabulary padded to 128; 8 tokens, 2 chunks
    matmuls = 2 * (4 * (8 + 8 + 2 + 2 + 2) + 8 * 4 + 4 * 128) * 8
    conv = 2 * 8 * 4 * (8 + 2 + 2)
    per_chunk = (4 * 4 * 2            # C B^T, causal half
                 + 2 * 4 * 4 * 4      # 2 heads, (C B^T L)(x dt), causal half
                 + 2 * 2 * 4 * 2 * 4  # 2 heads, chunk states
                 + 2 * 2 * 4 * 2 * 4)  # 2 heads, state to output
    assert COUNTS.forward_flops(config, 1, 8) == matmuls + conv + 2 * per_chunk
    assert COUNTS.train_flops_per_token(config, 8) == \
        3 * (matmuls + conv + 2 * per_chunk) / 8
    # a partial chunk is computed whole
    assert COUNTS.ssd_flops(config, 1, 7) == COUNTS.ssd_flops(config, 1, 8)
