"""Multi-pod dry-run: prove every (architecture x input-shape x mesh)
combination lowers AND compiles for the production meshes, and record what
the compiler reports of the compiled module (FLOPs, bytes accessed, memory,
collective op counts and bytes).

The production meshes are 512 virtual CPU devices. MUST be run as its own
process: the environment lines below must execute before jax initializes
devices. The CPU platform is claimed explicitly, so neither this process nor
one it is started from ever takes an attached TPU:

    PYTHONPATH=src python -m repro.launch.dryrun --arch olmo-1b --shape train_4k
    PYTHONPATH=src python -m repro.launch.dryrun --all --out experiments/dryrun
"""
import os

os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

import argparse
import json
import time
import traceback
from typing import Dict, Optional

import jax

from repro.configs import ARCHS, input_specs, pairs, supports
from repro.launch.mesh import make_production_mesh
from repro.launch.steps import (decode_cache_shapes, make_prefill_step,
                                make_serve_step, make_train_step)
from repro.models import registry
from repro.models.base import INPUT_SHAPES
from repro.optim.adamw import AdamW

from repro.launch.hlo_stats import collective_stats  # noqa: E402


def _sds_with(shapes, shardings):
    return jax.tree.map(
        lambda s, sh: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sh),
        shapes, shardings)


def lower_one(arch_id: str, shape_name: str, *, multi_pod: bool,
              strategy: str = "hier", fsdp: bool = True,
              remat: bool = True, mesh_shape: Optional[str] = None,
              overrides: Optional[Dict] = None) -> Dict:
    cfg = ARCHS[arch_id]
    if overrides:
        cfg = cfg.replace(**overrides)
    shape = INPUT_SHAPES[shape_name]
    if mesh_shape:
        from repro.launch.mesh import make_custom_mesh
        mesh = make_custom_mesh(mesh_shape)
    else:
        mesh = make_production_mesh(multi_pod=multi_pod)
    t0 = time.perf_counter()
    if shape.kind == "train":
        cfg = cfg.replace(remat=remat)
        opt = AdamW(lr=3e-4)
        step, pshard, oshard, bshard_fn = make_train_step(
            cfg, mesh, strategy=strategy, fsdp=fsdp, optimizer=opt)
        pshapes = jax.eval_shape(
            lambda k: registry.init(k, cfg), jax.random.key(0))
        oshapes = jax.eval_shape(opt.init, pshapes)
        bspecs = input_specs(cfg, shape)
        args = (_sds_with(pshapes, pshard),
                _sds_with(oshapes, oshard),
                _sds_with(bspecs, bshard_fn(bspecs)))
        lowered = step.lower(*args)
    elif shape.kind == "prefill":
        step, pshard, bshard_fn = make_prefill_step(cfg, mesh, fsdp=fsdp)
        pshapes = jax.eval_shape(
            lambda k: registry.init(k, cfg), jax.random.key(0))
        bspecs = input_specs(cfg, shape)
        lowered = step.lower(_sds_with(pshapes, pshard),
                             _sds_with(bspecs, bshard_fn(bspecs)))
    else:  # decode
        step, pshard, cshard_fn, bshard_fn = make_serve_step(cfg, mesh,
                                                             fsdp=fsdp)
        pshapes = jax.eval_shape(
            lambda k: registry.init(k, cfg), jax.random.key(0))
        specs = input_specs(cfg, shape)
        extras = {k: v for k, v in specs.items()
                  if k not in ("tokens", "pos")}
        cshapes = decode_cache_shapes(cfg, shape.global_batch, shape.seq_len,
                                      extras_shapes=extras or None)
        tok_b = {"tokens": specs["tokens"]}
        lowered = step.lower(
            _sds_with(pshapes, pshard),
            _sds_with(cshapes, cshard_fn(cshapes)),
            specs["pos"],
            _sds_with(tok_b, bshard_fn(tok_b))["tokens"])

    t_lower = time.perf_counter() - t0
    compiled = lowered.compile()
    t_compile = time.perf_counter() - t0 - t_lower

    cost = compiled.cost_analysis() or {}
    mem = compiled.memory_analysis()
    mem_d = {}
    for k in ("argument_size_in_bytes", "output_size_in_bytes",
              "temp_size_in_bytes", "generated_code_size_in_bytes",
              "peak_memory_in_bytes"):
        v = getattr(mem, k, None)
        if v is not None:
            mem_d[k] = int(v)
    coll = collective_stats(compiled.as_text())

    return {
        "arch": arch_id, "shape": shape_name,
        "mesh": mesh_shape or ("2x16x16" if multi_pod else "16x16"),
        "strategy": strategy, "fsdp": fsdp,
        "overrides": {k: str(v) for k, v in (overrides or {}).items()},
        "flops": float(cost.get("flops", -1)),
        "bytes_accessed": float(cost.get("bytes accessed", -1)),
        "collectives": coll,
        "collective_bytes": sum(d["bytes"] for d in coll.values()),
        "memory": mem_d,
        "lower_s": round(t_lower, 1), "compile_s": round(t_compile, 1),
        "params": registry.param_count(ARCHS[arch_id]),
        "active_params": registry.param_count(ARCHS[arch_id],
                                              active_only=True),
    }


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--both-meshes", action="store_true")
    ap.add_argument("--strategy", default="hier",
                    choices=["hier", "hier1", "allreduce"])
    ap.add_argument("--no-fsdp", action="store_true")
    ap.add_argument("--out", default=None, help="dir for per-pair JSON")
    ap.add_argument("--skip-existing", action="store_true")
    ap.add_argument("--mesh-shape", default=None,
                    help="custom mesh, e.g. 64x4 or 2x32x8 (§Perf)")
    ap.add_argument("--set", action="append", default=[],
                    help="ModelConfig override, e.g. --set n_layers=2")
    ap.add_argument("--tag", default="", help="suffix for the output JSON")
    args = ap.parse_args()

    overrides = {}
    for kv in args.set:
        k, v = kv.split("=", 1)
        try:
            import ast
            overrides[k] = ast.literal_eval(v)
        except (ValueError, SyntaxError):
            overrides[k] = v

    if args.all:
        todo = list(pairs())
    else:
        assert args.arch and args.shape, "--arch and --shape (or --all)"
        if not supports(args.arch, args.shape):
            print(f"SKIP {args.arch} x {args.shape}: unsupported "
                  "(see DESIGN.md §4)")
            return
        todo = [(args.arch, args.shape)]

    meshes = [False, True] if args.both_meshes else [args.multi_pod]
    failures = []
    for arch_id, shape_name in todo:
        for mp in meshes:
            mesh_name = args.mesh_shape or ("2x16x16" if mp else "16x16")
            tag = (f"{arch_id}__{shape_name}__{mesh_name}"
                   f"__{args.strategy}{'' if not args.no_fsdp else '__nofsdp'}"
                   f"{args.tag}")
            if args.out and args.skip_existing:
                path = os.path.join(args.out, tag + ".json")
                if os.path.exists(path):
                    print(f"skip {tag} (exists)")
                    continue
            print(f"=== {tag} ===", flush=True)
            try:
                res = lower_one(arch_id, shape_name, multi_pod=mp,
                                strategy=args.strategy, fsdp=not args.no_fsdp,
                                mesh_shape=args.mesh_shape,
                                overrides=overrides or None)
            except Exception as e:
                traceback.print_exc()
                failures.append((tag, repr(e)))
                continue
            print(json.dumps(
                {k: res[k] for k in ("flops", "bytes_accessed",
                                     "collective_bytes", "memory",
                                     "lower_s", "compile_s")}, indent=1),
                flush=True)
            if args.out:
                os.makedirs(args.out, exist_ok=True)
                with open(os.path.join(args.out, tag + ".json"), "w") as f:
                    json.dump(res, f, indent=1)
    if failures:
        print("FAILURES:")
        for tag, e in failures:
            print(" ", tag, e)
        raise SystemExit(1)
    print("dry-run complete: all combinations lowered + compiled")


if __name__ == "__main__":
    main()
