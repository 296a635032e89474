"""The main path's Pallas kernels compile for a TPU v5e at real widths.

Nothing runs: the TPU compiler, installed with jax, compiles for a chip
that is described (``v5e:2x2``) and not attached, so Mosaic refusals —
unsupported primitives, unaligned tiles, VMEM overruns — show up here
instead of on the chip. The topology is described inside a module fixture,
never at import: only one process may load the TPU library at a time, and
every test worker imports this file.
"""
import os
import re

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


def test_flash_backward_compiles_at_cell_shapes(one_chip):
    # train.olmo-1b's attention: batch 2, 16 heads, 2048 tokens, 128 wide
    qkv = ((2, 16, 2048, 128), jnp.float32)

    def fwd_bwd(q, k, v, do):
        o, vjp = jax.vjp(lambda q, k, v: ops.flash_attention(
            q, k, v, causal=True, interpret=False), q, k, v)
        return o, vjp(do)

    txt = _compiled_text(fwd_bwd, qkv, qkv, qkv, qkv, one_chip=one_chip)
    assert "%flash_attention_dkv" in txt
    assert "%flash_attention_dq" in txt


def test_attention_grad_takes_the_three_kernels(one_chip, monkeypatch):
    """A gradient of the model's attention at olmo-1b widths runs forward
    and backward in the kernels where they compile. The CPU's own dispatch
    (blockwise, interpreted kernels) is steered to the TPU's here."""
    from repro import kernels, obs
    from repro.configs import ARCHS
    from repro.kernels import flash_attention as fa
    from repro.models import layers
    monkeypatch.setattr(kernels, "pallas_compiles", lambda: True)
    monkeypatch.setattr(fa, "resolve_interpret", lambda interpret=None: False)
    cfg = ARCHS["olmo-1b"]
    d = cfg.d_model
    w = ((d, d), jnp.float32)

    def loss(wq, wk, wv, wo, x):
        p = {"wq": wq, "wk": wk, "wv": wv, "wo": wo}
        return jnp.sum(layers.apply_attention(p, cfg, x)[0])

    before = obs.snapshot()["counters"].get("attn.blockwise", 0)
    txt = _compiled_text(jax.grad(loss, argnums=(0, 1, 2, 3, 4)),
                         w, w, w, w, ((2, 2048, d), jnp.float32),
                         one_chip=one_chip)
    for name in ("flash_attention", "flash_attention_dkv",
                 "flash_attention_dq"):
        assert re.search(rf"%{name}(\.\d+)? = ", txt), name
    assert obs.snapshot()["counters"].get("attn.blockwise", 0) == before
    assert "while" not in txt      # no blockwise scan over query blocks


def _steer_ssd_kernels(monkeypatch):
    """Dispatch the SSD core as on one TPU chip: the CPU's own dispatch
    (``ssd_chunked``, interpreted kernels) is steered to the kernels,
    compiled."""
    from repro import kernels
    from repro.kernels import ssd
    monkeypatch.setattr(kernels, "pallas_compiles", lambda: True)
    monkeypatch.setattr(ssd, "resolve_interpret", lambda interpret=None: False)


def _ssd_counts():
    from repro import obs
    c = obs.snapshot()["counters"]
    return c.get("ssd.kernel", 0), c.get("ssd.chunked", 0)


def test_mamba2_block_grad_takes_the_ssd_kernels(one_chip, monkeypatch):
    """The gradient of a Mamba-2 block at train.mamba2-2.7b's widths runs
    its SSD core forward and backward in the kernels, and no (..., 256,
    256) float32 buffer -- a chunk's Q x Q decay or scores -- reaches HBM."""
    from repro.configs import ARCHS
    from repro.models import mamba2
    _steer_ssd_kernels(monkeypatch)
    cfg = ARCHS["mamba2-2.7b"].replace(dtype=jnp.float32)
    bp = jax.eval_shape(lambda k: mamba2.init_mamba_block(k, cfg),
                        jax.random.key(0))
    bp, h = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip),
        (bp, jax.ShapeDtypeStruct((2, 2048, cfg.d_model), jnp.float32)))

    def loss(bp, h):
        return jnp.sum(mamba2.apply_mamba_block(bp, cfg, h)[0])

    before = _ssd_counts()
    txt = jax.jit(jax.grad(loss, argnums=(0, 1))).lower(
        bp, h).compile().as_text()
    for name in ("ssd_chunk_fwd", "ssd_chunk_bwd"):
        assert re.search(rf"%{name}(\.\d+)? = ", txt), name
    assert not re.search(r"f32\[[\d,]*256,256\]", txt)
    kernel, chunked = (n - m for n, m in zip(_ssd_counts(), before))
    assert kernel >= 1 and chunked == 0


def test_mamba2_cell_train_step_fits_one_chip(one_chip, monkeypatch):
    """train.mamba2-2.7b's step as the benchmark builds it (12 of 64
    layers, float32 parameters and AdamW state, remat, 2 x 2048 tokens)
    compiles for one v5e, with the SSD kernels as one chip dispatches
    them, and its arguments and temporaries fit the chip's 16 GiB."""
    import numpy as np
    from jax.sharding import Mesh

    from repro.configs import ARCHS
    from repro.launch.steps import make_train_step
    from repro.models import registry
    from repro.optim import AdamW
    _steer_ssd_kernels(monkeypatch)
    cfg = ARCHS["mamba2-2.7b"].replace(n_layers=12, dtype=jnp.float32,
                                       remat=True)
    mesh = Mesh(np.array(list(one_chip.device_set)).reshape(1, 1),
                ("data", "model"))
    opt = AdamW()
    step, pshard, oshard, bshard = make_train_step(cfg, mesh, strategy="hier",
                                                   optimizer=opt)
    pshapes = jax.eval_shape(lambda k: registry.init(k, cfg),
                             jax.random.key(0))
    oshapes = jax.eval_shape(opt.init, pshapes)
    tok = jax.ShapeDtypeStruct((2, 2048), jnp.int32)
    batch = {"tokens": tok, "labels": tok}

    def placed(shapes, shardings):
        return jax.tree.map(lambda s, sh: jax.ShapeDtypeStruct(
            s.shape, s.dtype, sharding=sh), shapes, shardings)

    before = _ssd_counts()
    compiled = step.lower(placed(pshapes, pshard), placed(oshapes, oshard),
                          placed(batch, bshard(batch))).compile()
    assert [n - m for n, m in zip(_ssd_counts(), before)] == [1, 0]
    assert "%ssd_chunk_bwd" in compiled.as_text()
    mem = compiled.memory_analysis()
    total = mem.argument_size_in_bytes + mem.temp_size_in_bytes
    assert total < 16 * 2 ** 30, (mem.argument_size_in_bytes,
                                  mem.temp_size_in_bytes)


def _loops(jaxpr):
    """Every ``scan`` and ``while`` equation in a jaxpr, nested ones too."""
    for eqn in jaxpr.eqns:
        if eqn.primitive.name in ("scan", "while"):
            yield eqn
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _loops(sub)


def test_ssd_chunked_grad_loops_only_over_states():
    """The gradient of ``ssd_chunked`` at train.mamba2-2.7b's widths loops
    only over the (b, h, n, p) chunk states and their (b, h) decays: no
    loop carries, slices or closes over a chunk-length block of x, dt, B
    or C. The intra-chunk work is batched over all chunks. Only the jaxpr
    is built: nothing compiles."""
    from repro.models.mamba2 import ssd_chunked
    b, s, h, p, n, chunk = 2, 2048, 80, 64, 128, 256
    state = (b, h, n, p)

    def state_or_decay(sh):       # a decay may keep unit axes to broadcast
        return sh == state or (sh[:2] == (b, h) and set(sh[2:]) <= {1})

    def loss(x, dt, A, B, C, D):
        y, S = ssd_chunked(x, dt, A, B, C, D, chunk)
        return jnp.sum(y) + jnp.sum(S)

    f32 = jnp.float32
    shapes = [jax.ShapeDtypeStruct(sh, f32) for sh in
              ((b, s, h, p), (b, s, h), (h,), (b, s, n), (b, s, n), (h,))]
    jaxpr = jax.make_jaxpr(jax.grad(loss, argnums=tuple(range(6))))(*shapes)
    loops = list(_loops(jaxpr.jaxpr))
    assert loops, "the state recurrence is a scan"
    for eqn in loops:
        assert eqn.primitive.name == "scan", eqn.primitive.name
        nc, nk = eqn.params["num_consts"], eqn.params["num_carry"]
        consts = [v.aval.shape for v in eqn.invars[:nc]]
        carry = [v.aval.shape for v in eqn.invars[nc:nc + nk]]
        xs = [v.aval.shape[1:] for v in eqn.invars[nc + nk:]]
        ys = [v.aval.shape[1:] for v in eqn.outvars[nk:]]
        assert all(sh == state for sh in carry), carry
        assert all(map(state_or_decay, xs + ys)), (xs, ys)
        assert all(chunk not in sh and s not in sh for sh in consts), consts
