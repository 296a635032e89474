"""Config/registry coverage: assigned dims are exact, input specs build
and the step traces for every (arch x shape) pair at published widths,
reduced variants respect the smoke bounds."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh

from repro.configs import ARCHS, input_specs, pairs, reduced, supports
from repro.launch import steps
from repro.models import registry
from repro.models.base import INPUT_SHAPES
from repro.optim import AdamW

# the assigned table, verbatim from the brief; mamba2-2.7b's vocabulary is
# its checkpoint's config.json (50277 before padding)
ASSIGNED = {
    "mamba2-2.7b": dict(n_layers=64, d_model=2560, d_ff=0, vocab_size=50277,
                        ssm_state=128, family="ssm"),
    "seamless-m4t-medium": dict(n_layers=12, d_model=1024, n_heads=16,
                                n_kv_heads=16, d_ff=4096, vocab_size=256206,
                                family="audio"),
    "qwen2-moe-a2.7b": dict(n_layers=24, d_model=2048, n_heads=16,
                            n_kv_heads=16, d_ff=1408, vocab_size=151936,
                            n_experts=60, top_k=4, family="moe"),
    "arctic-480b": dict(n_layers=35, d_model=7168, n_heads=56, n_kv_heads=8,
                        d_ff=4864, vocab_size=32000, n_experts=128, top_k=2,
                        family="moe"),
    "olmo-1b": dict(n_layers=16, d_model=2048, n_heads=16, n_kv_heads=16,
                    d_ff=8192, vocab_size=50304, family="dense"),
    "qwen2.5-3b": dict(n_layers=36, d_model=2048, n_heads=16, n_kv_heads=2,
                       d_ff=11008, vocab_size=151936, family="dense"),
    "phi4-mini-3.8b": dict(n_layers=32, d_model=3072, n_heads=24,
                           n_kv_heads=8, d_ff=8192, vocab_size=200064,
                           family="dense"),
    "llama-3.2-vision-90b": dict(n_layers=100, d_model=8192, n_heads=64,
                                 n_kv_heads=8, d_ff=28672,
                                 vocab_size=128256, family="vlm"),
    "zamba2-7b": dict(n_layers=81, d_model=3584, n_heads=32, n_kv_heads=32,
                      d_ff=14336, vocab_size=32000, ssm_state=64,
                      family="hybrid"),
    "mistral-large-123b": dict(n_layers=88, d_model=12288, n_heads=96,
                               n_kv_heads=8, d_ff=28672, vocab_size=32768,
                               family="dense"),
}


@pytest.mark.parametrize("arch_id", sorted(ASSIGNED))
def test_assigned_dims_exact(arch_id):
    cfg = ARCHS[arch_id]
    for k, v in ASSIGNED[arch_id].items():
        assert getattr(cfg, k) == v, (arch_id, k, getattr(cfg, k), v)
    assert cfg.source, "every config must cite its source"


def test_pair_count_and_skips():
    ps = list(pairs())
    assert len(ps) == 32  # 10x4 - 8 long_500k skips
    assert not supports("mistral-large-123b", "long_500k")
    assert supports("mamba2-2.7b", "long_500k")
    assert supports("zamba2-7b", "long_500k")


@pytest.mark.parametrize("arch_id,shape_name", list(pairs()))
def test_input_specs_build(arch_id, shape_name):
    """ShapeDtypeStruct stand-ins exist for every model input of every
    supported pair — no device allocation."""
    cfg = ARCHS[arch_id]
    shape = INPUT_SHAPES[shape_name]
    specs = input_specs(cfg, shape)
    assert "tokens" in specs
    for leaf in jax.tree.leaves(specs):
        assert isinstance(leaf, jax.ShapeDtypeStruct)
    if shape.kind == "train":
        assert specs["tokens"].shape == (shape.global_batch, shape.seq_len)
    if shape.kind == "decode":
        assert specs["tokens"].shape == (shape.global_batch, 1)
        assert specs["pos"].shape == ()
    if cfg.family == "vlm":
        assert specs["image_embeds"].shape[1:] == (cfg.n_image_tokens,
                                                   cfg.d_vision)
    if cfg.family == "audio":
        assert specs["audio_frames"].shape[2] == cfg.d_audio


def _with(shapes, shardings):
    return jax.tree.map(
        lambda s, sh: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sh),
        shapes, shardings)


@pytest.mark.parametrize("arch_id,shape_name", list(pairs()))
def test_step_traces_at_published_widths(arch_id, shape_name):
    """The pair's train, prefill or serve step traces through its step
    builder on a one-device mesh at published widths (shapes only)."""
    cfg = ARCHS[arch_id]
    shape = INPUT_SHAPES[shape_name]
    mesh = Mesh(np.array(jax.devices()[:1]).reshape(1, 1), ("data", "model"))
    pshapes = jax.eval_shape(lambda k: registry.init(k, cfg),
                             jax.random.key(0))
    specs = input_specs(cfg, shape)
    if shape.kind == "train":
        step, pshard, oshard, bshard = steps.make_train_step(cfg, mesh)
        params = _with(pshapes, pshard)
        ostate = jax.eval_shape(AdamW(lr=3e-4).init, pshapes)
        new_params, _, loss = jax.eval_shape(
            step, params, _with(ostate, oshard), _with(specs, bshard(specs)))
        assert loss.shape == ()
        assert (jax.tree.map(lambda x: (x.shape, x.dtype), new_params)
                == jax.tree.map(lambda x: (x.shape, x.dtype), pshapes))
        return
    if shape.kind == "prefill":
        step, pshard, bshard = steps.make_prefill_step(cfg, mesh)
        logits, _ = jax.eval_shape(step, _with(pshapes, pshard),
                                   _with(specs, bshard(specs)))
    else:
        step, pshard, cshard, bshard = steps.make_serve_step(cfg, mesh)
        extras = {k: v for k, v in specs.items() if k not in ("tokens", "pos")}
        cshapes = steps.decode_cache_shapes(cfg, shape.global_batch,
                                            shape.seq_len,
                                            extras_shapes=extras or None)
        tokens = {"tokens": specs["tokens"]}
        logits, _ = jax.eval_shape(step, _with(pshapes, pshard),
                                   _with(cshapes, cshard(cshapes)),
                                   specs["pos"],
                                   _with(tokens, bshard(tokens))["tokens"])
    assert logits.shape[0] == shape.global_batch


@pytest.mark.parametrize("arch_id", sorted(ARCHS))
def test_reduced_respects_smoke_bounds(arch_id):
    cfg = reduced(ARCHS[arch_id])
    assert cfg.n_layers <= 5
    assert cfg.d_model <= 512
    if cfg.n_experts:
        assert cfg.n_experts <= 4
    assert cfg.dtype == jnp.float32
    assert cfg.family == ARCHS[arch_id].family


def test_vocab_padding_is_mxu_and_tp_aligned():
    for cfg in ARCHS.values():
        assert cfg.vocab_padded % 128 == 0
        assert cfg.vocab_padded % 16 == 0
        assert cfg.vocab_padded >= cfg.vocab_size
        assert cfg.vocab_padded - cfg.vocab_size < 128
