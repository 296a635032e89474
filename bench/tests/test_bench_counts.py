"""The operation and byte counts of bench/counts against independent
counts at a small size: XLA's cost analysis of the reference's unscanned
layer-by-layer programs, and the program's own parameter shapes."""
from __future__ import annotations

import copy

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import harness, program

COUNTS = harness.load_module("counts", "dense")
REF = harness.load_module("reference", "dense")
SMALL = dict(n_layers=2, d_model=256, n_heads=4, n_kv_heads=4, d_ff=1024,
             vocab_size=1000)


@pytest.fixture(scope="module")
def config():
    c = copy.deepcopy(harness.load_json(
        harness.BENCH_DIR / "configs" / "olmo-1b.json"))
    c["model"].update(SMALL, dtype="float32")
    return c


def _flops(fn, *args) -> float:
    cost = jax.jit(fn).lower(*args).compile().cost_analysis()
    cost = cost[0] if isinstance(cost, list) else cost
    return float(cost["flops"])


def _ref_shapes(config):
    D = REF.dims(config)
    key = jax.random.key(0)
    emb = jax.eval_shape(lambda k: REF.embed_weights(k, D), key)
    layer = jax.eval_shape(lambda k: REF.layer_weights(k, 0, D), key)
    return D, emb, layer


def test_matmul_params_match_the_programs_shapes(config):
    from repro.models import registry
    cfg = program.model_config(config)
    shapes = jax.eval_shape(lambda k: registry.init(k, cfg), jax.random.key(0))
    flat = jax.tree_util.tree_flatten_with_path(shapes)[0]
    blocks = sum(int(np.prod(x.shape)) for p, x in flat
                 if program.leaf_name(p).startswith("blocks/"))
    tok = shapes["embed"]["tok"].shape  # tied: the output layer is tok.T
    assert COUNTS.block_matmul_params(config) == blocks
    assert COUNTS.matmul_params(config) == blocks + tok[0] * tok[1]


@pytest.mark.parametrize("batch,seq", [(2, 128), (1, 256)])
def test_forward_flops_match_cost_analysis(config, batch, seq):
    D, emb, layer = _ref_shapes(config)
    h = jax.ShapeDtypeStruct((batch, seq, D.d), jnp.float32)
    xla = (_flops(lambda w, x: REF.block(w, x, D), layer, h) * D.layers
           + _flops(lambda e, x: REF.matmul(x, e["tok"].T, D), emb, h))
    # the reference computes the full score and value products; the count
    # is of the causal half, so add the other half back
    want = (COUNTS.forward_flops(config, batch, seq)
            + COUNTS.attention_flops(config, batch, seq))
    assert xla == pytest.approx(want, rel=0.03)


def test_train_flops_match_cost_analysis(config):
    batch, seq = 2, 128
    D, emb, layer = _ref_shapes(config)
    h = jax.ShapeDtypeStruct((batch, seq, D.d), jnp.float32)

    def block_train(w, x, dy):
        y, back = jax.vjp(lambda w_, x_: REF.block(w_, x_, D), w, x)
        return y, back(dy)

    def head_train(e, x, dz):
        z, back = jax.vjp(lambda e_, x_: REF.matmul(x_, e_["tok"].T, D), e, x)
        return z, back(dz)

    dz = jax.ShapeDtypeStruct((batch, seq, D.vocab_padded), jnp.float32)
    xla = (_flops(block_train, layer, h, h) * D.layers
           + _flops(head_train, emb, h, dz))
    want = (COUNTS.train_flops_per_token(config, seq) * batch * seq
            + 3 * COUNTS.attention_flops(config, batch, seq))
    assert xla == pytest.approx(want, rel=0.05)
