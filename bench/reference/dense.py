"""Plain float32 reference of a dense decoder-only transformer (OLMo).

It imports nothing of the program. It makes its own weights from the weight
seed by the recipe that defines them for this benchmark: keys split as
``split(key, 3)`` -> embedding, blocks; the token embedding
``normal(vocab_padded, d) * 0.02``; each block's key from
``split(blocks_key, n_layers)``, split into attention (q, k, v, o) and MLP
(wi, wg, wo) keys; every matrix ``normal(d_in, d_out) * d_in ** -0.5``;
each rounded to the configuration's served type and then held in float32.

The architecture follows OLMo (arXiv:2402.00838): non-parametric layer norm
(eps 1e-5) before attention and before the SwiGLU MLP, rotary embeddings on
queries and keys (halves rotated), causal softmax attention, no biases, a
final layer norm, and the token embedding tied to the output layer. Every
matrix product runs at ``Precision.HIGHEST``.

Training follows the configuration's ``optimizer`` group: AdamW with
global-norm clipping, its rate rising linearly from 0 over ``warmup_steps``.

Memory: the weights are made and applied one layer at a time in jitted
per-layer programs; Adam's moments live on the host between steps, so a
float32 training step of a 1.2e9-parameter model fits one 16 GB chip.

``mode="fp8"`` is the control: both operands of every matrix product are
rounded to float8 e4m3 with one scale per tensor (the forward pass only;
the backward pass takes them as they are).
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding
from jax.sharding import PartitionSpec as P

F32 = jnp.float32
HIGHEST = jax.lax.Precision.HIGHEST
E4M3_MAX = 448.0
SERVED_TYPES = {"bfloat16": jnp.bfloat16, "float32": jnp.float32}


@dataclasses.dataclass(frozen=True)
class Dims:
    d: int
    heads: int
    kv_heads: int
    head_dim: int
    ff: int
    vocab: int
    vocab_padded: int
    layers: int
    rope_theta: float
    tied: bool
    dtype: str
    mode: str = "f32"


def dims(config: Dict, mode: str = "f32") -> Dims:
    m = config["model"]
    if m.get("norm") != "nonparametric_ln" or m.get("mlp") != "swiglu":
        raise ValueError("this reference is OLMo's block: non-parametric "
                         "layer norm and a SwiGLU MLP")
    if m.get("qkv_bias") or m.get("sliding_window"):
        raise ValueError("no biases and full causal attention only")
    hd = m.get("head_dim") or m["d_model"] // m["n_heads"]
    dtype = m["dtype"] if isinstance(m["dtype"], str) \
        else jnp.dtype(m["dtype"]).name
    return Dims(d=m["d_model"], heads=m["n_heads"],
                kv_heads=m.get("n_kv_heads", m["n_heads"]), head_dim=hd,
                ff=m["d_ff"], vocab=m["vocab_size"],
                vocab_padded=-(-m["vocab_size"] // 128) * 128,
                layers=m["n_layers"], rope_theta=float(m["rope_theta"]),
                tied=bool(m["tie_embeddings"]), dtype=dtype, mode=mode)


# ---------------------------------------------------------------------------
# weights
# ---------------------------------------------------------------------------


def _served(x, D: Dims):
    return x.astype(SERVED_TYPES[D.dtype]).astype(F32)


def _dense(key, d_in: int, d_out: int, D: Dims):
    return _served(jax.random.normal(key, (d_in, d_out)) * d_in ** -0.5, D)


def embed_weights(key, D: Dims) -> Dict[str, jax.Array]:
    ks = jax.random.split(jax.random.split(key, 3)[0], 2)
    w = {"tok": _served(jax.random.normal(ks[0], (D.vocab_padded, D.d))
                        * 0.02, D)}
    if not D.tied:
        w["unembed"] = _dense(ks[1], D.d, D.vocab_padded, D)
    return w


def layer_weights(key, layer, D: Dims) -> Dict[str, jax.Array]:
    lk = jax.random.split(jax.random.split(key, 3)[1], D.layers)[layer]
    ka, km = jax.random.split(lk, 2)
    ka = jax.random.split(ka, 4)
    km = jax.random.split(km, 3)
    q, kv = D.heads * D.head_dim, D.kv_heads * D.head_dim
    return {"attn/wq": _dense(ka[0], D.d, q, D),
            "attn/wk": _dense(ka[1], D.d, kv, D),
            "attn/wv": _dense(ka[2], D.d, kv, D),
            "attn/wo": _dense(ka[3], q, D.d, D),
            "mlp/wi": _dense(km[0], D.d, D.ff, D),
            "mlp/wg": _dense(km[1], D.d, D.ff, D),
            "mlp/wo": _dense(km[2], D.ff, D.d, D)}


# ---------------------------------------------------------------------------
# the model
# ---------------------------------------------------------------------------


@jax.custom_vjp
def _fp8(x):
    scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / E4M3_MAX
    return (x / scale).astype(jnp.float8_e4m3fn).astype(F32) * scale


def _fp8_fwd(x):
    return _fp8(x), None


def _fp8_bwd(_, g):
    return (g,)


_fp8.defvjp(_fp8_fwd, _fp8_bwd)


def _ops(D: Dims, *xs):
    return tuple(_fp8(x) for x in xs) if D.mode == "fp8" else xs


def matmul(a, b, D: Dims):
    a, b = _ops(D, a, b)
    return jnp.matmul(a, b, precision=HIGHEST)


def einsum(spec: str, a, b, D: Dims):
    a, b = _ops(D, a, b)
    return jnp.einsum(spec, a, b, precision=HIGHEST)


def layer_norm(x, eps: float = 1e-5):
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), axis=-1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + eps)


def rope(x, theta: float):
    """x: (b, s, h, hd) at positions 0..s-1; the two halves rotate."""
    hd = x.shape[-1]
    freqs = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=F32) / hd))
    ang = jnp.arange(x.shape[1], dtype=F32)[:, None] * freqs
    cos, sin = jnp.cos(ang)[None, :, None], jnp.sin(ang)[None, :, None]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def block(w, h, D: Dims):
    b, s, _ = h.shape
    x = layer_norm(h)
    q = matmul(x, w["attn/wq"], D).reshape(b, s, D.heads, D.head_dim)
    k = matmul(x, w["attn/wk"], D).reshape(b, s, D.kv_heads, D.head_dim)
    v = matmul(x, w["attn/wv"], D).reshape(b, s, D.kv_heads, D.head_dim)
    q, k = rope(q, D.rope_theta), rope(k, D.rope_theta)
    rep = D.heads // D.kv_heads
    k, v = jnp.repeat(k, rep, axis=2), jnp.repeat(v, rep, axis=2)
    scores = einsum("bqhd,bkhd->bhqk", q * D.head_dim ** -0.5, k, D)
    causal = jnp.tril(jnp.ones((s, s), bool))
    probs = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), axis=-1)
    o = einsum("bhqk,bkhd->bqhd", probs, v, D).reshape(b, s, -1)
    h = h + matmul(o, w["attn/wo"], D)
    x = layer_norm(h)
    gate = jax.nn.silu(matmul(x, w["mlp/wi"], D)) * matmul(x, w["mlp/wg"], D)
    return h + matmul(gate, w["mlp/wo"], D)


def _out_matrix(emb, D: Dims):
    return emb["tok"].T if D.tied else emb["unembed"]


def logits(emb, h, D: Dims):
    """Next-token logits over the real vocabulary."""
    return matmul(layer_norm(h), _out_matrix(emb, D), D)[..., :D.vocab]


def loss(emb, h, tokens, D: Dims):
    """Mean next-token cross-entropy; the labels are the tokens."""
    z = logits(emb, h[:, :-1], D)
    gold = jnp.take_along_axis(z, tokens[:, 1:, None], axis=-1)[..., 0]
    return jnp.mean(jax.nn.logsumexp(z, axis=-1) - gold)


# ---------------------------------------------------------------------------
# jitted pieces, one program per (dims, shape)
# ---------------------------------------------------------------------------


@functools.lru_cache(maxsize=None)
def _programs(D: Dims, rep: Optional[NamedSharding]):
    out = {"out_shardings": rep} if rep is not None else {}
    return {
        "embed_w": jax.jit(lambda k: embed_weights(k, D), **out),
        "layer_w": jax.jit(lambda k, i: layer_weights(k, i, D), **out),
        "embed": jax.jit(lambda emb, t: jnp.take(emb["tok"], t, axis=0)),
        "block": jax.jit(lambda w, h: block(w, h, D)),
        "block_vjp": jax.jit(
            lambda w, h, dh: jax.vjp(lambda w_, h_: block(w_, h_, D),
                                     w, h)[1](dh)),
        "head": jax.jit(jax.value_and_grad(
            lambda emb, h, t: loss(emb, h, t, D), argnums=(0, 1))),
        "embed_grad": jax.jit(
            lambda dh, t: jnp.zeros((D.vocab_padded, D.d), F32).at[t].add(dh),
            **out),
        "sq": jax.jit(lambda tree: jax.tree.map(
            lambda x: jnp.sum(jnp.square(x)), tree)),
        "diff_sq": jax.jit(lambda a, b: jax.tree.map(
            lambda x, y: jnp.sum(jnp.square(x - y)), a, b)),
    }


@functools.lru_cache(maxsize=None)
def _adam(hp: Tuple[Tuple[str, float], ...]):
    o = dict(hp)

    warmup = o.get("warmup_steps", 0)

    def update(p, g, m, v, t, scale):
        g = g * scale
        m = o["b1"] * m + (1 - o["b1"]) * g
        v = o["b2"] * v + (1 - o["b2"]) * g * g
        mhat = m / (1 - o["b1"] ** t)
        vhat = v / (1 - o["b2"] ** t)
        lr = o["lr"] * (jnp.minimum(1.0, t / warmup) if warmup else 1.0)
        p = p - lr * (mhat / (jnp.sqrt(vhat) + o["eps"])
                      + o["weight_decay"] * p)
        return p, m, v

    return jax.jit(update)


def _mesh(devices) -> Tuple[NamedSharding, NamedSharding]:
    mesh = Mesh(np.array(list(devices)), ("data",))
    return NamedSharding(mesh, P()), NamedSharding(mesh, P("data"))


def _leaf(name: str) -> str:
    return name if name.startswith("embed/") else "blocks/" + name


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
    data chip's rows only (the exchange between chips left out).
    """
    D = dims(config, mode)
    hp = tuple(sorted(config["optimizer"].items()))
    clip = float(config["optimizer"]["grad_clip"])
    rep, rows = _mesh(devices)
    prog = _programs(D, rep)
    adam = _adam(hp)
    key = jax.random.key(weight_seed)
    emb = prog["embed_w"](key)
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
            lval, _, _ = _grads(prog, emb, layers,
                                jax.device_put(tokens, rows), D)
            _, g_emb, g_layers = _grads(prog, emb, layers,
                                        jax.device_put(local, rep), D)
        else:
            lval, g_emb, g_layers = _grads(prog, emb, layers,
                                           jax.device_put(tokens, rows), D)
        losses.append(float(lval))
        sq = {"embed/" + k: float(v) for k, v in prog["sq"](g_emb).items()}
        for g in g_layers:
            for k, v in prog["sq"](g).items():
                sq[_leaf(k)] = sq.get(_leaf(k), 0.0) + float(v)
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

        emb = {k: step(-1, k, emb[k], g_emb[k]) for k in emb}
        for i in range(D.layers):
            layers[i] = {k: step(i, k, layers[i][k], g_layers[i][k])
                         for k in layers[i]}
        del g_emb, g_layers

    moments.clear()
    upd = {"embed/" + k: float(v) for k, v in
           prog["diff_sq"](emb, prog["embed_w"](key)).items()}
    for i in range(D.layers):
        for k, v in prog["diff_sq"](layers[i],
                                    prog["layer_w"](key, i)).items():
            upd[_leaf(k)] = upd.get(_leaf(k), 0.0) + float(v)
    return {"losses": losses, "grad_norms": grad_norms,
            "update_norms": {k: float(np.sqrt(v)) for k, v in upd.items()}}


def _grads(prog, emb, layers, tokens, D: Dims):
    """Loss and gradients, layer by layer: the forward keeps each layer's
    input; the backward recomputes one layer at a time."""
    hs = [prog["embed"](emb, tokens)]
    for w in layers:
        hs.append(prog["block"](w, hs[-1]))
    lval, (g_emb, dh) = prog["head"](emb, hs.pop(), tokens)
    g_layers = [None] * len(layers)
    for i in reversed(range(len(layers))):
        g_layers[i], dh = prog["block_vjp"](layers[i], hs.pop(), dh)
    g_emb = dict(g_emb)
    g_emb["tok"] = g_emb["tok"] + prog["embed_grad"](dh, tokens)
    return lval, g_emb, g_layers
