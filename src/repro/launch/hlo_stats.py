"""HLO-text collective statistics: count and result bytes of each
collective op in a compiled module's text. No jax imports and no env side
effects, so tests import it without ``repro.launch.dryrun``'s 512-device
set-up; the dry-run records these per lowered pair."""
from __future__ import annotations

import re
from typing import Dict

_DTYPE_BYTES = {"f64": 8, "f32": 4, "bf16": 2, "f16": 2, "s64": 8, "u64": 8,
                "s32": 4, "u32": 4, "s16": 2, "u16": 2, "s8": 1, "u8": 1,
                "pred": 1, "f8e4m3fn": 1, "f8e5m2": 1}

_COLL_RE = re.compile(
    r"=\s+((?:\([^)]*\))|(?:[a-z0-9]+\[[^\]]*\]))[^=]*?"
    r"\b(all-gather|all-reduce|reduce-scatter|all-to-all|collective-permute)"
    r"(?:-start|-done)?\(")
_SHAPE_RE = re.compile(r"([a-z0-9]+)\[([0-9,]*)\]")


def _type_bytes(type_str: str) -> int:
    total = 0
    for dt, dims in _SHAPE_RE.findall(type_str):
        if dt not in _DTYPE_BYTES:
            continue
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        total += n * _DTYPE_BYTES[dt]
    return total


def collective_stats(hlo_text: str) -> Dict[str, Dict[str, float]]:
    """Sum result-operand sizes of every collective op in the HLO. `-done`
    ops are skipped so async pairs aren't double counted.

    NOTE result-size is a proxy: for ring all-reduce the wire traffic is
    ~2x the result, for all-gather ~1x, for reduce-scatter the result is
    1/n of the input. These stats are for op-mix inspection and
    before/after comparison of the same program, not wire bytes.
    """
    out: Dict[str, Dict[str, float]] = {}
    for m in _COLL_RE.finditer(hlo_text):
        type_str, op = m.group(1), m.group(2)
        if f"{op}-done(" in m.group(0):
            continue
        d = out.setdefault(op, {"count": 0, "bytes": 0.0})
        d["count"] += 1
        d["bytes"] += _type_bytes(type_str)
    return out
