"""Pure-jnp oracles for every Pallas kernel (independent implementations:
naive/sequential forms, not the chunked/blockwise algorithms the kernels
use — so agreement is a real check)."""
from __future__ import annotations

import jax
import jax.numpy as jnp


def ref_aggregate(shards):
    """(n, L) -> (L,) mean in f32."""
    return jnp.mean(shards.astype(jnp.float32), axis=0).astype(shards.dtype)


def ref_aggregate_apply(shards, param, lr: float):
    g = jnp.mean(shards.astype(jnp.float32), axis=0)
    return (param.astype(jnp.float32) - lr * g).astype(param.dtype)


def ref_attention(q, k, v, *, causal: bool = True, window: int = 0):
    """Naive full-softmax attention. q: (b, h, sq, d), k/v: (b, h, sk, d)."""
    b, h, sq, d = q.shape
    sk = k.shape[2]
    s = jnp.einsum("bhqd,bhkd->bhqk", q.astype(jnp.float32),
                   k.astype(jnp.float32)) * d ** -0.5
    qpos = jnp.arange(sq)[:, None]
    kpos = jnp.arange(sk)[None, :]
    mask = jnp.ones((sq, sk), bool)
    if causal:
        mask &= qpos >= kpos
    if window:
        mask &= qpos - kpos < window
    s = jnp.where(mask[None, None], s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhqk,bhkd->bhqd", p,
                      v.astype(jnp.float32)).astype(q.dtype)


def ref_ssd(x, dt, A, B, C, D, initial_state=None):
    """Sequential (per-token) SSD recurrence — the O(s) definition.
    x: (b, s, h, p)  dt: (b, s, h)  A, D: (h,)  B, C: (b, s, n);
    initial_state: (b, h, n, p), zeros if None."""
    b, s, h, p = x.shape
    n = B.shape[-1]
    xf = x.astype(jnp.float32)
    dtf = dt.astype(jnp.float32)
    Af = A.astype(jnp.float32)
    Bf = B.astype(jnp.float32)
    Cf = C.astype(jnp.float32)

    def step(S, t):
        xt, dtt, Bt, Ct = xf[:, t], dtf[:, t], Bf[:, t], Cf[:, t]
        dA = jnp.exp(dtt * Af)                                # (b, h)
        S = S * dA[..., None, None] + jnp.einsum(
            "bn,bhp->bhnp", Bt, xt * dtt[..., None])
        y = jnp.einsum("bn,bhnp->bhp", Ct, S)
        return S, y

    S0 = (jnp.zeros((b, h, n, p), jnp.float32) if initial_state is None
          else initial_state.astype(jnp.float32))
    S, ys = jax.lax.scan(step, S0, jnp.arange(s))
    y = ys.transpose(1, 0, 2, 3) + D.astype(jnp.float32)[None, None, :, None] * xf
    return y.astype(x.dtype), S
