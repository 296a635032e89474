"""Operation counts of a Mamba-2 language model (SSD mixer, one group of
B and C), from the configuration file's ``model`` group and the shapes of
the work.

Model FLOPs count each matrix product once, as the model requires it:
2 per multiply-add; the five input projections (z, x, B, C, dt), the
output projection and the tied output layer; the depthwise convolution;
the SSD core as the published chunked algorithm needs it at
``ssm_chunk`` (arXiv:2405.21060, section 6): per chunk of Q tokens the
intra-chunk C B^T and its product with x dt as causal halves, each head's
chunk state B^T (x dt) and its state-to-output C S. The embedding lookup,
norms, gates and the recurrence between chunks (elementwise) are not
counted, nor anything recomputed. Training is three times the forward
pass.
"""
from __future__ import annotations

from typing import Dict


def _m(config: Dict) -> Dict:
    return config["model"]


def vocab_padded(config: Dict) -> int:
    return -(-_m(config)["vocab_size"] // 128) * 128


def d_inner(config: Dict) -> int:
    m = _m(config)
    return m.get("ssm_expand", 2) * m["d_model"]


def nheads(config: Dict) -> int:
    return d_inner(config) // _m(config)["ssm_headdim"]


def conv_width(config: Dict) -> int:
    return _m(config).get("ssm_conv_width", 4)


def block_matmul_params(config: Dict) -> int:
    """Weights that enter a matrix product, all layers."""
    m = _m(config)
    d, di, n = m["d_model"], d_inner(config), m["ssm_state"]
    return m["n_layers"] * (d * (2 * di + 2 * n + nheads(config)) + di * d)


def matmul_params(config: Dict) -> int:
    """Block weights plus the output layer (d x padded vocabulary)."""
    return block_matmul_params(config) + _m(config)["d_model"] * vocab_padded(config)


def params(config: Dict) -> int:
    """Every parameter: the products' weights, the embedding (tied to the
    output layer), each layer's two norm scales, conv weights and biases,
    dt_bias, A_log and D, and the final norm."""
    m = _m(config)
    d, di, n, nh = m["d_model"], d_inner(config), m["ssm_state"], nheads(config)
    conv = (conv_width(config) + 1) * (di + 2 * n)
    per_layer = d + di + conv + 3 * nh
    return matmul_params(config) + m["n_layers"] * per_layer + d


def conv_flops(config: Dict, batch: int, seq: int) -> float:
    """Depthwise convolution over x, B and C, all layers."""
    m = _m(config)
    return (2.0 * batch * seq * conv_width(config)
            * (d_inner(config) + 2 * m["ssm_state"]) * m["n_layers"])


def ssd_flops(config: Dict, batch: int, seq: int) -> float:
    """The chunked SSD core's products, all layers; a partial last chunk
    is computed as a whole one."""
    m = _m(config)
    q, n, p, nh = m["ssm_chunk"], m["ssm_state"], m["ssm_headdim"], nheads(config)
    chunks = -(-seq // q)
    per_chunk = (q * q * n              # C B^T, causal half
                 + nh * q * q * p       # (C B^T . decay) (x dt), causal half
                 + 2 * nh * q * n * p   # chunk states B^T (x dt)
                 + 2 * nh * q * n * p)  # state to output C S
    return float(batch * chunks * per_chunk * m["n_layers"])


def forward_flops(config: Dict, batch: int, seq: int) -> float:
    return (2.0 * matmul_params(config) * batch * seq
            + conv_flops(config, batch, seq) + ssd_flops(config, batch, seq))


def train_flops_per_token(config: Dict, seq: int) -> float:
    return 3.0 * forward_flops(config, 1, seq) / seq
