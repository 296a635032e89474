"""The dry-run's HLO collective parser (``repro.launch.hlo_stats``). The
dry-run itself needs 512 host devices and its own process; the full sweep
runs as ``python -m repro.launch.dryrun --all``."""

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
