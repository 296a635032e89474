"""Plain float32 reference of a Mamba-2 language model (SSD mixer).

It imports nothing of the program. It makes its own weights from the weight
seed by the recipe that defines them for this benchmark: keys split as
``split(key, 2)`` -> embedding, blocks; the token embedding
``normal(vocab_padded, d) * 0.02`` from the first of ``split(embed_key, 2)``;
each block's key from ``split(blocks_key, n_layers)``, split in twelve:
z, x, B, C and dt projections (0-4), A (5), the x and B-C conv weights
(6, 7), the output projection (8), dt (9) and the two conv biases (10, 11).
Every matrix is ``normal(d_in, d_out) * d_in ** -0.5`` and the conv weights
``normal(W, c) * W ** -0.5``. The rest follows mamba_ssm's Mamba2 init:
``A_log = log U[1, 16]``; ``D = 1``; dt log-uniform in [1e-3, 1e-1],
floored at 1e-4, stored as ``dt_bias = dt + log(-expm1(-dt))`` (the inverse
softplus); conv biases ``U(-W ** -0.5, W ** -0.5)``, torch Conv1d's default;
norm scales 1. Each is rounded to the configuration's served type and then
held in float32.

The architecture follows Mamba-2 (arXiv:2405.21060) with one group of B and
C: RMSNorm (eps 1e-5) before each mixer, input projections to z, x, B, C
and dt, a causal depthwise convolution of width W with bias and SiLU over
x and over B with C, ``dt = softplus(dt_raw + dt_bias)``, ``A = -exp(A_log)``,
the SSD ``y = SSM(A, B, C)(x dt) + D x``, a gated RMSNorm of ``y * silu(z)``,
the output projection, a residual, a final RMSNorm, and the token embedding
tied to the output layer. Every matrix product runs at
``Precision.HIGHEST``.

Departures from the published model and from the program, each deliberate:

- SSD is the full-sequence quadratic (masked) form,
  ``y = ((C B^T) * L) (x dt)`` with ``L = exp(segsum(dt A))`` over all
  positions of a sequence, not the chunked scan the program runs; the
  segment sums are the stable ones of the paper's minimal SSD listing (a
  masked cumulative sum per query column, taken with
  ``lax.associative_scan``: additions only), not differences of one long
  cumulative sum. It is computed a block of heads at a time, each block
  under ``jax.checkpoint``, so its (b, heads, s, s) tensors exist for one
  block only.
- The published in_proj is five matrices and the conv over xBC two (x;
  B with C): the same products and sums.
- AdamW decays every leaf, as the program's optimizer does (the published
  code exempts A_log, D, norms and biases).

Training follows the configuration's ``optimizer`` group: AdamW with
global-norm clipping, its rate rising linearly from 0 over
``warmup_steps`` (the dense reference's AdamW and float8 rounding).

Memory: the weights are made and applied one layer at a time in jitted
per-layer programs, and each layer's backward recomputes its forward from
its input (each layer checkpointed); Adam's moments live on the host
between steps, so a float32 training step fits one 16 GB chip once the
program's state is freed.

``mode="fp8"`` is the control: both operands of every matrix product are
rounded to float8 e4m3 with one scale per tensor (the forward pass only;
the backward pass takes them as they are). ``fault="no_carry"`` zeroes the
SSM state at every ``ssm_chunk`` boundary, as a chunked scan that drops
its carry would.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from bench.reference import dense
from bench.reference.dense import einsum, matmul

F32 = jnp.float32
SERVED_TYPES = {"bfloat16": jnp.bfloat16, "float32": jnp.float32}
HEAD_BLOCK = 16            # heads whose (b, s, s) tensors exist at once
DT_MIN, DT_MAX, DT_FLOOR = 1e-3, 1e-1, 1e-4
EPS = 1e-5


@dataclasses.dataclass(frozen=True)
class Dims:
    d: int
    d_inner: int
    heads: int
    head_dim: int
    state: int
    conv: int
    chunk: int
    vocab: int
    vocab_padded: int
    layers: int
    dtype: str
    mode: str = "f32"
    no_carry: bool = False

    @property
    def head_block(self) -> int:
        return max(k for k in range(1, min(HEAD_BLOCK, self.heads) + 1)
                   if self.heads % k == 0)


def dims(config: Dict, mode: str = "f32", no_carry: bool = False) -> Dims:
    m = config["model"]
    if m.get("norm") != "rmsnorm" or not m.get("tie_embeddings"):
        raise ValueError("this reference is Mamba-2's model: RMSNorm and a "
                         "tied output layer")
    di = m.get("ssm_expand", 2) * m["d_model"]
    dtype = m["dtype"] if isinstance(m["dtype"], str) \
        else jnp.dtype(m["dtype"]).name
    return Dims(d=m["d_model"], d_inner=di, heads=di // m["ssm_headdim"],
                head_dim=m["ssm_headdim"], state=m["ssm_state"],
                conv=m.get("ssm_conv_width", 4), chunk=m["ssm_chunk"],
                vocab=m["vocab_size"],
                vocab_padded=-(-m["vocab_size"] // 128) * 128,
                layers=m["n_layers"], dtype=dtype, mode=mode,
                no_carry=no_carry)


# ---------------------------------------------------------------------------
# weights
# ---------------------------------------------------------------------------


def _served(x, D: Dims):
    return x.astype(SERVED_TYPES[D.dtype]).astype(F32)


def _dense(key, d_in: int, d_out: int, D: Dims):
    return _served(jax.random.normal(key, (d_in, d_out)) * d_in ** -0.5, D)


def top_weights(key, D: Dims) -> Dict[str, jax.Array]:
    """The embedding (tied to the output layer) and the final norm."""
    ek = jax.random.split(jax.random.split(key, 2)[0], 2)[0]
    return {"embed/tok": _served(jax.random.normal(ek, (D.vocab_padded, D.d))
                                 * 0.02, D),
            "final_norm/scale": jnp.ones((D.d,), F32)}


def layer_weights(key, layer, D: Dims) -> Dict[str, jax.Array]:
    lk = jax.random.split(jax.random.split(key, 2)[1], D.layers)[layer]
    k = jax.random.split(lk, 12)
    W, di, n, nh = D.conv, D.d_inner, D.state, D.heads
    lo, hi = jnp.log(DT_MIN), jnp.log(DT_MAX)
    dt = jnp.maximum(jnp.exp(jax.random.uniform(k[9], (nh,), minval=lo,
                                                maxval=hi)), DT_FLOOR)

    def conv_bias(kk, c):
        return _served(jax.random.uniform(kk, (c,), minval=-W ** -0.5,
                                          maxval=W ** -0.5), D)

    return {"ln/scale": jnp.ones((D.d,), F32),
            "wz": _dense(k[0], D.d, di, D),
            "wx": _dense(k[1], D.d, di, D),
            "wB": _dense(k[2], D.d, n, D),
            "wC": _dense(k[3], D.d, n, D),
            "wdt": _dense(k[4], D.d, nh, D),
            "dt_bias": _served(dt + jnp.log(-jnp.expm1(-dt)), D),
            "A_log": _served(jnp.log(jax.random.uniform(
                k[5], (nh,), minval=1.0, maxval=16.0)), D),
            "D": jnp.ones((nh,), F32),
            "conv_x": _served(jax.random.normal(k[6], (W, di)) * W ** -0.5, D),
            "conv_BC": _served(jax.random.normal(k[7], (W, 2 * n))
                               * W ** -0.5, D),
            "conv_x_bias": conv_bias(k[10], di),
            "conv_BC_bias": conv_bias(k[11], 2 * n),
            "gate_ln/scale": jnp.ones((di,), F32),
            "wo": _dense(k[8], di, D.d, D)}


# ---------------------------------------------------------------------------
# the model
# ---------------------------------------------------------------------------


def rmsnorm(x, scale):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + EPS) \
        * scale


def conv(x, w, bias):
    """Causal depthwise convolution: out[t] = bias + sum_i w[i] x[t-W+1+i]."""
    W, s = w.shape[0], x.shape[1]
    xp = jnp.pad(x, ((0, 0), (W - 1, 0), (0, 0)))
    return jax.nn.silu(bias + sum(w[i] * xp[:, i:i + s] for i in range(W)))


def segsum(a, D: Dims):
    """a: (..., s) -> (..., s, s) with [i, j] = a[j+1] + ... + a[i] for
    i >= j and -inf above the diagonal (and, with ``no_carry``, between
    chunks): each query column's own cumulative sum, never a difference."""
    s = a.shape[-1]
    i, j = jnp.arange(s)[:, None], jnp.arange(s)[None, :]
    cols = jnp.where(i > j, a[..., :, None], 0.0)        # [i, j] = a[i]
    sums = jax.lax.associative_scan(jnp.add, cols, axis=-2)
    keep = i >= j
    if D.no_carry:
        keep &= (i // D.chunk) == (j // D.chunk)
    return jnp.where(keep, sums, -jnp.inf)


def ssd(x, dt, A, B, C, D: Dims):
    """x: (b,s,h,p) dt: (b,s,h) A: (h,) B, C: (b,s,n) -> (b,s,h,p), the
    quadratic form without the D skip, a block of heads at a time."""
    b, s, h, p = x.shape
    hb = D.head_block
    cb = einsum("bin,bjn->bij", C, B, D)                  # (b, s, s)

    @jax.checkpoint
    def heads(args):
        da, xdt = args                                    # (b,s,hb) (b,s,hb,p)
        L = jnp.exp(segsum(da.transpose(0, 2, 1), D))     # (b, hb, s, s)
        return einsum("bhij,bjhp->bihp", cb[:, None] * L, xdt, D)

    def blocks(t):                                        # (b,s,h,..) -> (nb,b,s,hb,..)
        t = t.reshape((b, s, h // hb, hb) + t.shape[3:])
        return jnp.moveaxis(t, 2, 0)

    y = jax.lax.map(heads, (blocks(dt * A), blocks(x * dt[..., None])))
    return jnp.moveaxis(y, 0, 2).reshape(b, s, h, p)


def block(w, h, D: Dims):
    b, s, _ = h.shape
    u = rmsnorm(h, w["ln/scale"])
    z = matmul(u, w["wz"], D)
    x = conv(matmul(u, w["wx"], D), w["conv_x"], w["conv_x_bias"])
    bc = conv(jnp.concatenate([matmul(u, w["wB"], D), matmul(u, w["wC"], D)],
                              -1), w["conv_BC"], w["conv_BC_bias"])
    B, C = bc[..., :D.state], bc[..., D.state:]
    dt = jax.nn.softplus(matmul(u, w["wdt"], D) + w["dt_bias"])
    A = -jnp.exp(w["A_log"])
    x = x.reshape(b, s, D.heads, D.head_dim)
    y = ssd(x, dt, A, B, C, D) + w["D"][:, None] * x
    y = rmsnorm(y.reshape(b, s, D.d_inner) * jax.nn.silu(z), w["gate_ln/scale"])
    return h + matmul(y, w["wo"], D)


def logits(top, h, D: Dims):
    """Next-token logits over the real vocabulary."""
    z = matmul(rmsnorm(h, top["final_norm/scale"]), top["embed/tok"].T, D)
    return z[..., :D.vocab]


def loss(top, h, tokens, D: Dims):
    """Mean next-token cross-entropy; the labels are the tokens."""
    z = logits(top, h[:, :-1], D)
    gold = jnp.take_along_axis(z, tokens[:, 1:, None], axis=-1)[..., 0]
    return jnp.mean(jax.nn.logsumexp(z, axis=-1) - gold)


# ---------------------------------------------------------------------------
# jitted pieces, one program per (dims, shape)
# ---------------------------------------------------------------------------


@functools.lru_cache(maxsize=None)
def _programs(D: Dims, rep):
    out = {"out_shardings": rep}
    return {
        "top_w": jax.jit(lambda k: top_weights(k, D), **out),
        "layer_w": jax.jit(lambda k, i: layer_weights(k, i, D), **out),
        "embed": jax.jit(lambda top, t: jnp.take(top["embed/tok"], t, axis=0)),
        "block": jax.jit(lambda w, h: block(w, h, D)),
        "block_vjp": jax.jit(
            lambda w, h, dh: jax.vjp(lambda w_, h_: block(w_, h_, D),
                                     w, h)[1](dh)),
        "head": jax.jit(jax.value_and_grad(
            lambda top, h, t: loss(top, h, t, D), argnums=(0, 1))),
        "embed_grad": jax.jit(
            lambda dh, t: jnp.zeros((D.vocab_padded, D.d), F32).at[t].add(dh),
            **out),
        "sq": jax.jit(lambda tree: jax.tree.map(
            lambda x: jnp.sum(jnp.square(x)), tree)),
        "diff_sq": jax.jit(lambda a, b: jax.tree.map(
            lambda x, y: jnp.sum(jnp.square(x - y)), a, b)),
    }


# ---------------------------------------------------------------------------
# training: the first three AdamW steps
# ---------------------------------------------------------------------------


def train(config: Dict, weight_seed: int, batches: Sequence[np.ndarray],
          devices, *, mode: str = "f32",
          fault: Optional[str] = None) -> Dict:
    """-> {"losses", "grad_norms", "update_norms"} of the first steps.

    ``grad_norms`` are the norms of each leaf's first gradient after
    clipping, the leaves being the program's (layers stacked);
    ``update_norms`` the norms of each leaf's change over all the steps.
    ``fault`` plants a fault in the reference, for calibration and tests:
    ``"half_batch"`` takes the loss and gradient over the first half of
    each batch's rows; ``"local_grad"`` takes the gradient over the first
    data chip's rows only (the exchange between chips left out);
    ``"no_carry"`` zeroes the SSM state at every chunk boundary.
    """
    D = dims(config, mode, no_carry=fault == "no_carry")
    hp = tuple(sorted(config["optimizer"].items()))
    clip = float(config["optimizer"]["grad_clip"])
    rep, rows = dense._mesh(devices)
    prog = _programs(D, rep)
    adam = dense._adam(hp)
    key = jax.random.key(weight_seed)
    top = prog["top_w"](key)
    layers = [prog["layer_w"](key, i) for i in range(D.layers)]
    moments: Dict[Tuple[int, str], Tuple[np.ndarray, np.ndarray]] = {}
    losses: List[float] = []
    grad_norms: Dict[str, float] = {}
    n_dev = len(list(devices))

    for t, tokens in enumerate(batches, start=1):
        tokens = np.asarray(tokens)
        if fault == "half_batch":
            tokens = tokens[: tokens.shape[0] // 2]
        if fault == "local_grad":
            local = tokens[: tokens.shape[0] // n_dev]
            lval, _, _ = _grads(prog, top, layers,
                                jax.device_put(tokens, rows))
            _, g_top, g_layers = _grads(prog, top, layers,
                                        jax.device_put(local, rep))
        else:
            lval, g_top, g_layers = _grads(prog, top, layers,
                                           jax.device_put(tokens, rows))
        losses.append(float(lval))
        sq = {k: float(v) for k, v in prog["sq"](g_top).items()}
        for g in g_layers:
            for k, v in prog["sq"](g).items():
                sq["blocks/" + k] = sq.get("blocks/" + k, 0.0) + float(v)
        scale = min(1.0, clip / (float(np.sqrt(sum(sq.values()))) + 1e-9))
        if t == 1:
            grad_norms = {k: float(np.sqrt(v)) * scale for k, v in sq.items()}

        def step(i, name, p, g):
            m, v = moments.get((i, name), (None, None))
            if m is None:
                m = v = jnp.zeros_like(p)
            p, m, v = adam(p, g, m, v, float(t), scale)
            if t < len(batches):
                moments[(i, name)] = (np.asarray(jax.device_get(m)),
                                      np.asarray(jax.device_get(v)))
            return p

        top = {k: step(-1, k, top[k], g_top[k]) for k in top}
        for i in range(D.layers):
            layers[i] = {k: step(i, k, layers[i][k], g_layers[i][k])
                         for k in layers[i]}
        del g_top, g_layers

    moments.clear()
    upd = {k: float(v) for k, v in
           prog["diff_sq"](top, prog["top_w"](key)).items()}
    for i in range(D.layers):
        for k, v in prog["diff_sq"](layers[i],
                                    prog["layer_w"](key, i)).items():
            upd["blocks/" + k] = upd.get("blocks/" + k, 0.0) + float(v)
    return {"losses": losses, "grad_norms": grad_norms,
            "update_norms": {k: float(np.sqrt(v)) for k, v in upd.items()}}


def _grads(prog, top, layers, tokens):
    """Loss and gradients, layer by layer: the forward keeps each layer's
    input; the backward recomputes one layer at a time."""
    hs = [prog["embed"](top, tokens)]
    for w in layers:
        hs.append(prog["block"](w, hs[-1]))
    lval, (g_top, dh) = prog["head"](top, hs.pop(), tokens)
    g_layers = [None] * len(layers)
    for i in reversed(range(len(layers))):
        g_layers[i], dh = prog["block_vjp"](layers[i], hs.pop(), dh)
    g_top = dict(g_top)
    g_top["embed/tok"] = g_top["embed/tok"] + prog["embed_grad"](dh, tokens)
    return lval, g_top, g_layers
