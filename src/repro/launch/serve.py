"""Serving launcher: one batch of same-length requests through the serving
engine's jitted prefill and KV/SSM-cache decode on the default device:

    PYTHONPATH=src python -m repro.launch.serve --arch mamba2-2.7b --reduced \
        --requests 4 --prompt-len 32 --gen 16

The batch runs twice: the first call compiles, the second is timed.
"""
from __future__ import annotations

import argparse

import numpy as np

from repro.configs import ARCHS, reduced
from repro.core.rng import base_stream
from repro.launch.compile_cache import enable_compile_cache
from repro.serving import ServingEngine


def serve(cfg, *, n_requests: int, prompt_len: int, gen: int, seed: int = 0):
    """-> (tokens (n_requests, gen), prefill_s, decode_s) of the warm run."""
    engine = ServingEngine(cfg, seed=seed)
    prompts = base_stream(seed).randint(
        0, cfg.vocab_size, size=(n_requests, prompt_len)).astype(np.int32)
    batch = engine.batch_inputs(prompts)
    engine.generate(batch, gen)
    out = engine.generate(batch, gen)
    return out.tokens, out.prefill_s, out.decode_s


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True, choices=sorted(ARCHS))
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--requests", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--gen", type=int, default=16)
    args = ap.parse_args()

    enable_compile_cache()
    cfg = ARCHS[args.arch]
    if args.reduced:
        cfg = reduced(cfg)
    toks, tp, td = serve(cfg, n_requests=args.requests,
                         prompt_len=args.prompt_len, gen=args.gen)
    per_tok = td / max(args.gen - 1, 1) / args.requests
    print(f"prefill {tp*1e3:.0f} ms; decode {td*1e3:.0f} ms "
          f"({per_tok*1e3:.1f} ms/token/request)")
    print("generated:", toks[0, :12].tolist(), "...")


if __name__ == "__main__":
    main()
