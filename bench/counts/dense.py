"""Operation and byte counts of a dense decoder (OLMo family), from the
configuration file's ``model`` group and the shapes of the work.

Model FLOPs count each matrix product once, as the model requires it:
2 per multiply-add, the output layer counted and the embedding lookup not,
causal attention as half of the full score and value products, nothing
recomputed. Training is three times the forward pass.
"""
from __future__ import annotations

from typing import Dict

def _m(config: Dict) -> Dict:
    return config["model"]


def head_dim(config: Dict) -> int:
    m = _m(config)
    return m.get("head_dim") or m["d_model"] // m["n_heads"]


def vocab_padded(config: Dict) -> int:
    return -(-_m(config)["vocab_size"] // 128) * 128


def block_matmul_params(config: Dict) -> int:
    """Weights that enter a matrix product, all layers."""
    m = _m(config)
    d, hd = m["d_model"], head_dim(config)
    q, kv = m["n_heads"] * hd, m.get("n_kv_heads", m["n_heads"]) * hd
    mlp = (3 if m.get("mlp", "swiglu") == "swiglu" else 2) * d * m["d_ff"]
    return m["n_layers"] * (2 * d * q + 2 * d * kv + mlp)


def matmul_params(config: Dict) -> int:
    """Block weights plus the output layer (d x padded vocabulary)."""
    return block_matmul_params(config) + _m(config)["d_model"] * vocab_padded(config)


def attention_flops(config: Dict, batch: int, seq: int) -> float:
    """Causal score and value products of a forward pass over ``seq``."""
    m = _m(config)
    return 2.0 * batch * seq * seq * m["n_heads"] * head_dim(config) * m["n_layers"]


def forward_flops(config: Dict, batch: int, seq: int) -> float:
    return 2.0 * matmul_params(config) * batch * seq + attention_flops(config, batch, seq)


def train_flops_per_token(config: Dict, seq: int) -> float:
    return 3.0 * forward_flops(config, 1, seq) / seq
