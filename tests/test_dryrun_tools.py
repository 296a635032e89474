"""Dry-run tooling: HLO collective parser + analytic roofline model
invariants. (The dry-run itself needs 512 host devices and its own process;
the full sweep is exercised by `python -m repro.launch.dryrun --all`.)"""
import numpy as np
import pytest

from benchmarks import flops_model as FM
from repro.configs import ARCHS

HLO = """
  %ag = bf16[16,512,1024]{2,1,0} all-gather(bf16[1,512,1024]{2,1,0} %p0), replica_groups={...}
  %ar.1 = f32[2048]{0} all-reduce(f32[2048]{0} %x), to_apply=%add
  %rs = (f32[128]{0}, f32[128]{0}) reduce-scatter(f32[2048]{0} %a, f32[2048]{0} %b), dimensions={0}
  %a2a = bf16[4,64]{1,0} all-to-all(bf16[4,64]{1,0} %y), dimensions={0}
  %cp = u32[8]{0} collective-permute(u32[8]{0} %z), source_target_pairs={{0,1}}
  %start = f32[64]{0} all-gather-start(f32[8]{0} %w)
  %done = f32[64]{0} all-gather-done(f32[64]{0} %start)
"""


def test_collective_parser_counts_and_bytes():
    # hlo_stats, not dryrun: importing dryrun sets XLA_FLAGS to 512 host
    # devices, which every later test in this worker process would get
    from repro.launch.hlo_stats import collective_stats
    stats = collective_stats(HLO)
    assert stats["all-gather"]["count"] == 2          # ag + ag-start
    assert stats["all-reduce"]["count"] == 1
    assert stats["reduce-scatter"]["count"] == 1
    assert stats["all-to-all"]["count"] == 1
    assert stats["collective-permute"]["count"] == 1
    assert stats["all-gather"]["bytes"] == 16 * 512 * 1024 * 2 + 64 * 4
    assert stats["all-reduce"]["bytes"] == 2048 * 4
    assert stats["reduce-scatter"]["bytes"] == 2 * 128 * 4  # tuple result


@pytest.mark.parametrize("arch_id", sorted(ARCHS))
@pytest.mark.parametrize("shape", ["train_4k", "prefill_32k", "decode_32k"])
def test_roofline_terms_positive_and_finite(arch_id, shape):
    t = FM.step_terms(ARCHS[arch_id], shape)
    assert t.flops > 0 and t.hbm_bytes > 0 and t.coll_bytes > 0
    assert np.isfinite([t.t_compute, t.t_memory, t.t_collective]).all()
    assert t.dominant() in ("compute", "memory", "collective")


def test_roofline_levers_move_the_right_terms():
    cfg = ARCHS["mistral-large-123b"]
    base = FM.step_terms(cfg, "train_4k")
    # sequence parallelism cuts collective only
    sp = FM.step_terms(cfg.replace(seq_shard=True), "train_4k")
    assert sp.coll_bytes < base.coll_bytes
    assert sp.flops == base.flops
    # dots remat cuts compute, raises HBM
    dots = FM.step_terms(cfg.replace(remat_policy="dots"), "train_4k")
    assert dots.flops < base.flops
    assert dots.hbm_bytes > base.hbm_bytes
    # more DP, less TP cuts per-device TP-activation collectives
    reshape = FM.step_terms(cfg, "train_4k", n_data=32, n_model=8)
    assert reshape.t_collective < base.t_collective


def test_moe_dispatch_levers():
    cfg = ARCHS["qwen2-moe-a2.7b"]
    base = FM.step_terms(cfg, "train_4k")
    small_group = FM.step_terms(cfg.replace(moe_group=512), "train_4k")
    assert small_group.flops < base.flops * 0.5
    padded = FM.step_terms(cfg.replace(moe_group=512, moe_pad_experts=64),
                           "train_4k")
    assert padded.flops < small_group.flops


def test_useful_ratio_bounded():
    for arch_id in ARCHS:
        m = FM.model_flops_per_step(ARCHS[arch_id], "train_4k")
        t = FM.step_terms(ARCHS[arch_id], "train_4k")
        assert 0 < m / (t.flops * 256) <= 1.01, arch_id
