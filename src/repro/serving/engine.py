"""Real-model batched serving engine: collects requests, runs them through
prefill + KV/SSM-cache decode in adaptive batches on any zoo model.

The policy layer (batcher.py) decides batch size/timeouts from the cost
model; this engine executes a batch with real JAX and proves greedy decode
is batching-invariant (a request's tokens don't depend on its batchmates).
"""
from __future__ import annotations

import dataclasses
from typing import Dict, List

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.models import registry
from repro.models.base import ModelConfig


@dataclasses.dataclass
class Request:
    rid: int
    prompt: np.ndarray          # (prompt_len,) int32
    max_new_tokens: int


@dataclasses.dataclass
class Completion:
    rid: int
    tokens: np.ndarray


@dataclasses.dataclass
class Generation:
    """One batch's greedy decode. ``last_logits`` are the final decode
    step's (b, 1, vocab_padded) logits; ``prefill_s`` is host wall time to
    the first token (prefill + its argmax, blocked on: the
    ``smlt.serve.prefill`` span), ``decode_s`` the time of the remaining
    ``gen - 1`` decode steps, blocked on (the ``smlt.serve.decode`` span,
    around one ``smlt.serve.decode_step`` per step)."""
    tokens: jax.Array
    last_logits: jax.Array
    prefill_s: float
    decode_s: float


class ServingEngine:
    """Fixed-shape batched engine. Requests in one batch must share a
    prompt length (the batcher buckets by length): the zoo models take no
    per-row pad mask, so left-padding would leak pad tokens into
    attention. Per-row masks/ragged batching are the next increment."""

    def __init__(self, cfg: ModelConfig, params=None, seed: int = 0):
        self.cfg = cfg
        self.params = params if params is not None else registry.init(
            jax.random.key(seed), cfg)

        def prefill_step(p, b, max_seq):
            return registry.prefill(p, cfg, b, max_seq=max_seq)

        def decode_step(p, c, pos, tok):
            return registry.decode_step(p, cfg, c, pos, tok)

        self._prefill = jax.jit(prefill_step, static_argnums=2)
        self._decode = jax.jit(decode_step)

    def batch_inputs(self, prompts: np.ndarray) -> Dict[str, jax.Array]:
        """(b, s) int32 prompts -> model inputs, with the config's modality
        stubs (zero image embeddings / audio frames) where it has them."""
        cfg, b = self.cfg, prompts.shape[0]
        batch = {"tokens": jnp.asarray(prompts, jnp.int32)}
        if cfg.family == "vlm":
            batch["image_embeds"] = jnp.zeros(
                (b, cfg.n_image_tokens, cfg.d_vision), cfg.dtype)
        if cfg.family == "audio":
            batch["audio_frames"] = jnp.zeros(
                (b, cfg.n_audio_frames, cfg.d_audio), cfg.dtype)
        return batch

    def prefill(self, batch, max_seq: int):
        """-> (logits, cache) over the whole prompt, cache sized max_seq."""
        return self._prefill(self.params, batch, max_seq)

    def generate(self, batch, gen: int) -> Generation:
        vocab = self.cfg.vocab_size
        plen = batch["tokens"].shape[1]
        with obs.span("smlt.serve.prefill") as prefill:
            logits, cache = self.prefill(batch, plen + gen)
            tok = jax.block_until_ready(
                jnp.argmax(logits[:, -1:, :vocab], axis=-1))
        out = [tok]
        with obs.span("smlt.serve.decode") as decode:
            for t in range(gen - 1):
                with obs.span("smlt.serve.decode_step"):
                    logits, cache = self._decode(self.params, cache,
                                                 jnp.int32(plen + t), tok)
                    tok = jnp.argmax(logits[:, :, :vocab], axis=-1)
                out.append(tok)
            tokens = jax.block_until_ready(jnp.concatenate(out, axis=1))
        obs.count("serve.tokens", tokens.size)
        return Generation(tokens, logits[:, -1:], prefill.seconds,
                          decode.seconds)

    def serve_batch(self, requests: List[Request]) -> List[Completion]:
        lengths = {len(r.prompt) for r in requests}
        if len(lengths) != 1:
            # the zoo models take no per-row pad mask: left-padding would
            # leak pad tokens into shorter prompts' attention and hand
            # decode_step a wrong pos for them, silently corrupting output
            raise ValueError(
                "serve_batch requires all requests to share a prompt "
                f"length (got lengths {sorted(lengths)}); bucket requests "
                "by length before batching")
        gen = max(r.max_new_tokens for r in requests)
        prompts = np.stack([r.prompt for r in requests])
        gen_toks = np.asarray(self.generate(self.batch_inputs(prompts),
                                            gen).tokens)
        return [Completion(r.rid, gen_toks[i, :r.max_new_tokens])
                for i, r in enumerate(requests)]
