"""Reference op features of an MLA + shared-expert MoE step graph,
written from the DeepSeek-V2 layer equations (arXiv 2405.04434,
§2.1-2.2) and the definitions of the generic LM op types; each reads
the op's params and tensors in the graph's JSON form.

FLOPs count a multiply-accumulate as 2; an attention score adds 5 for
its scale and softmax (max, subtract, sum, divide; the exponential is
not counted) and, in the masked prefill form, 1 for the causal select.
Bytes are ``elem_bytes`` (2, bfloat16) an element moved.
"""
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


def _norm(op: Op) -> List[float]:
    x = op.node["inputs"][0]
    return [op.size(x), op.shape(x)[-1], 5.0 * op.size(x)]


def _rope(op: Op) -> List[float]:
    x = op.size(op.node["inputs"][0])
    return [x, 6.0 * x]


def _embedding(op: Op) -> List[float]:
    width, tokens = op.param("width", 1), op.param("tokens", 1)
    return [op.param("vocab", 1), width, tokens, 2.0 * tokens * width]


def _elementwise_lm(op: Op) -> List[float]:
    x = op.node["inputs"][0]
    return [op.size(x), op.shape(x)[-1]]


def _collective(op: Op) -> List[float]:
    return [op.param("bytes", 0), op.param("participants", 1)]


def _mla_prefill(op: Op) -> List[float]:
    """The chunk's queries (qk dim nope + rope) against K/V decompressed
    over prefix + chunk: Q K^T, then P V of width v, per head."""
    c, p, h = op.param("chunk", 1), op.param("prefix", 0), op.param("heads", 1)
    qk, rope = op.param("qk_head_dim", 1), op.param("rope_dim", 0)
    v, eb = op.param("v_head_dim", 1), op.param("elem_bytes", 2)
    keys = p + c
    qkt = 2.0 * h * c * keys * qk
    pv = 2.0 * h * c * keys * v
    softmax = 6.0 * h * c * keys
    # per key: each head's nope key and value, and the one shared rope key
    kv_bytes = float(eb * keys * (h * (qk - rope) + h * v + rope))
    return [c, p, keys, h, qk, v, kv_bytes, qkt + pv + softmax]


def _mla_decode(op: Op) -> List[float]:
    """Absorbed decode: per head, the absorbed query (latent + rope)
    against every cached entry, then the weights over the latents."""
    n, total = op.param("seqs", 1), op.param("kv_total", 1)
    longest, h = op.param("kv_max", 1), op.param("heads", 1)
    r, rope = op.param("latent_dim", 1), op.param("rope_dim", 0)
    eb = op.param("elem_bytes", 2)
    scores = 2.0 * h * total * (r + rope)
    mix = 2.0 * h * total * r
    softmax = 5.0 * h * total
    return [n, float(eb * total * (r + rope)), total, longest, h, r, rope,
            scores + mix + softmax]


def _moe_router(op: Op) -> List[float]:
    t, d = op.param("tokens", 1), op.param("d_model", 1)
    e, k = op.param("experts", 1), op.param("top_k", 1)
    eb = op.param("elem_bytes", 2)
    return [t, d, e, k, float(eb * d * e), 2.0 * t * d * e + 4.0 * t * e]


def _moe_routed(op: Op) -> List[float]:
    """gate, up (d -> f) and down (f -> d) for each routed token, the
    SwiGLU between (5 an element) and the gate weighting and sum back
    (2 an element of d)."""
    held, active = op.param("experts_held", 1), op.param("active", 1)
    routed, top = op.param("routed", 0), op.param("max_load", 0)
    d, f = op.param("d_model", 1), op.param("d_ff", 1)
    eb = op.param("elem_bytes", 2)
    flops = 3 * 2.0 * routed * d * f + 5.0 * routed * f + 2.0 * routed * d
    return [held, active, routed, top, d, f, float(eb * 3 * active * d * f),
            float(eb * 2 * routed * d), flops]


FEATURES = {
    "embedding": _embedding,
    "norm": _norm,
    "matmul": _matmul,
    "rope": _rope,
    "elementwise_lm": _elementwise_lm,
    "collective": _collective,
    "mla_prefill": _mla_prefill,
    "mla_decode": _mla_decode,
    "moe_router": _moe_router,
    "moe_routed": _moe_routed,
}
