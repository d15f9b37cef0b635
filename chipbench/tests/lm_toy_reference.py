"""Reference op features of the test-only LM graphs, written from the
definitions of the program's LM-family feature functions: each reads
the op's params and tensors in the graph's JSON form.  The tests
register it as ``chipbench.reference_lm_toy``."""
from __future__ import annotations

from typing import List

import numpy as np

from chipbench.reference import Op


def _nbytes(op: Op, tids: List[int]) -> float:
    return float(sum(
        op.size(t) * np.dtype(op.g["tensors"][str(t)]["dtype"]).itemsize
        for t in tids))


def _matmul(op: Op) -> List[float]:
    m, n, k = op.param("m", 1), op.param("n", 1), op.param("k", 1)
    b = op.param("batch", 1)
    return [m, n, k, b, _nbytes(op, op.node["inputs"]),
            _nbytes(op, op.node["outputs"]), 2.0 * b * m * n * k]


def _attention(op: Op) -> List[float]:
    b, q_len = op.param("batch", 1), op.param("q_len", 1)
    kv_len, heads = op.param("kv_len", 1), op.param("heads", 1)
    kv_heads = op.param("kv_heads", heads)
    head_dim = op.param("head_dim", 64)
    window = op.param("window", 0) or kv_len
    eff_kv = min(kv_len, window)
    return [b, q_len, kv_len, heads, kv_heads, head_dim, window,
            2.0 * b * kv_heads * eff_kv * head_dim * 2,
            4.0 * b * heads * q_len * eff_kv * head_dim]


def _norm(op: Op) -> List[float]:
    x = op.node["inputs"][0]
    return [op.size(x), op.shape(x)[-1], 5.0 * op.size(x)]


def _moe_gmm(op: Op) -> List[float]:
    experts, top_k = op.param("experts", 1), op.param("top_k", 1)
    tokens, d_model = op.param("tokens", 1), op.param("d_model", 1)
    d_ff = op.param("d_ff", 1)
    capacity = op.param("capacity", tokens * top_k // max(1, experts))
    return [experts, top_k, tokens, d_model, d_ff, capacity,
            2.0 * 3 * experts * capacity * d_model * d_ff]


FEATURES = {
    "matmul": _matmul,
    "attention": _attention,
    "norm": _norm,
    "moe_gmm": _moe_gmm,
}
