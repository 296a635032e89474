"""AdamW optimizer in pure JAX (no optax dependency).

State layout matches the param pytree leaf-for-leaf so the SMLT sharding
rules (ZeRO-style data-sharded optimizer state for the ``hier`` strategy)
apply uniformly: opt state leaves inherit the spec of their parameter.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, NamedTuple, Optional

import jax
import jax.numpy as jnp

from repro import obs


class AdamWState(NamedTuple):
    step: jnp.ndarray
    mu: Any
    nu: Any


@dataclasses.dataclass(frozen=True)
class AdamW:
    lr: float = 1e-3
    b1: float = 0.9
    b2: float = 0.999
    eps: float = 1e-8
    weight_decay: float = 0.01
    grad_clip: float = 1.0
    schedule: Optional[Callable] = None  # step -> lr multiplier

    def init(self, params) -> AdamWState:
        zeros = lambda p: jnp.zeros_like(p, dtype=jnp.float32)
        return AdamWState(step=jnp.zeros((), jnp.int32),
                          mu=jax.tree.map(zeros, params),
                          nu=jax.tree.map(zeros, params))

    @obs.scoped("optimizer")
    def update(self, grads, state: AdamWState, params):
        step = state.step + 1
        if self.grad_clip:
            with jax.named_scope("clip"):
                gnorm = jnp.sqrt(sum(
                    jnp.sum(jnp.square(g.astype(jnp.float32)))
                    for g in jax.tree.leaves(grads)))
                scale = jnp.minimum(1.0, self.grad_clip / (gnorm + 1e-9))
                grads = jax.tree.map(lambda g: g * scale, grads)
        b1, b2 = self.b1, self.b2
        mu = jax.tree.map(lambda m, g: b1 * m + (1 - b1) * g.astype(jnp.float32),
                          state.mu, grads)
        nu = jax.tree.map(
            lambda v, g: b2 * v + (1 - b2) * jnp.square(g.astype(jnp.float32)),
            state.nu, grads)
        sf = step.astype(jnp.float32)
        bc1 = 1 - b1 ** sf
        bc2 = 1 - b2 ** sf
        lr = self.lr * (self.schedule(step) if self.schedule else 1.0)

        def upd(p, m, v):
            mhat = m / bc1
            vhat = v / bc2
            delta = mhat / (jnp.sqrt(vhat) + self.eps) + self.weight_decay * p.astype(jnp.float32)
            return (p.astype(jnp.float32) - lr * delta).astype(p.dtype)

        new_params = jax.tree.map(upd, params, mu, nu)
        return new_params, AdamWState(step=step, mu=mu, nu=nu)


def apply_sgd(params, grads, lr: float):
    """Plain SGD used by the semantic serverless trainer examples."""
    return jax.tree.map(lambda p, g: p - lr * g.astype(p.dtype), params, grads)
