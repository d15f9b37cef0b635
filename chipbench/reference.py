"""Plain float64 reference of a served prediction (paper §4.2).

Independent of the program: it reads a graph in its JSON wire form and
the bank in the JSON the hub saves, computes each op's features from
the definitions of paper Table 3 (with the repository's documented
extensions: activation cost tier and fused-tail features), walks every
tree node by node in float64 on standardized features, and composes

    e2e = overhead + overhead_per_kernel * kernels
          + op_sum_scale * sum(per-op predictions)

An op type without a predictor in the bank contributes 0.

``precision="bfloat16"`` is the control: the same walk with features,
thresholds (in raw feature units) and leaf values rounded to
bfloat16, the narrower storage a later change might be tempted by.
"""
from __future__ import annotations

import hashlib
import json
from typing import Any, Dict, List, Sequence, Tuple

import ml_dtypes
import numpy as np

# Cost tiers of activation and element-wise kinds (the repository's
# feature definition; 1.5 for a kind not listed).
KIND_COST = {
    None: 0.0, "": 0.0, "identity": 0.0, "copy": 0.0, "neg": 0.5, "abs": 0.5,
    "relu": 1.0, "relu6": 1.0, "add": 1.0, "sub": 1.0, "maximum": 1.0,
    "minimum": 1.0, "square": 1.0, "mul": 1.0, "greater": 1.0, "less": 1.0,
    "equal": 1.0, "hswish": 2.0, "sqrt": 2.0, "div": 2.0,
    "sigmoid": 3.0, "swish": 3.0, "exp": 3.0, "log": 3.0, "pow": 3.0,
    "tanh": 3.0, "gelu": 3.0,
}


def kind_cost(kind: Any) -> float:
    if isinstance(kind, str) and "@" in kind:
        kind = kind.split("@", 1)[0]
    return KIND_COST.get(kind, 1.5)


def fingerprint(graph_json: Dict[str, Any]) -> str:
    blob = json.dumps(graph_json, sort_keys=True).encode()
    return hashlib.sha256(blob).hexdigest()[:16]


class _Op:
    __slots__ = ("g", "node")

    def __init__(self, g: Dict[str, Any], node: Dict[str, Any]):
        self.g = g
        self.node = node

    def param(self, key: str, default: Any = None) -> Any:
        for k, v in self.node["params"]:
            if k == key:
                return v
        return default

    def shape(self, tid: int) -> List[int]:
        return self.g["tensors"][str(tid)]["shape"]

    def size(self, tid: int) -> int:
        n = 1
        for d in self.shape(tid):
            n *= int(d)
        return n

    def nhwc(self, tid: int) -> Tuple[int, int, int, int]:
        s = self.shape(tid)
        if len(s) == 4:
            return s[0], s[1], s[2], s[3]
        if len(s) == 3:
            return 1, s[0], s[1], s[2]
        if len(s) == 2:
            return s[0], 1, 1, s[1]
        raise ValueError(f"unsupported shape {s}")

    def fused_tail(self) -> List[float]:
        fused = self.node.get("fused", [])
        return [float(len(fused)), float(sum(kind_cost(k) for k in fused))]


def _conv(op: _Op, grouped: bool) -> List[float]:
    x, y = op.node["inputs"][0], op.node["outputs"][0]
    _, ih, iw, ic = op.nhwc(x)
    _, oh, ow, oc = op.nhwc(y)
    kh, kw = op.param("kernel_h", 1), op.param("kernel_w", 1)
    stride, groups = op.param("stride", 1), op.param("groups", 1)
    if op.node["op_type"] == "dwconv2d":
        groups = ic
    cpg = max(1, ic // max(1, groups))
    flops = 2.0 * oh * ow * oc * kh * kw * cpg
    vals = [ih, iw, ic, oh, ow, stride, kh, kw, oc, op.size(x), op.size(y),
            kh * kw * cpg * oc, flops]
    if grouped:
        vals.append(groups)
    return vals + [kind_cost(op.param("act"))] + op.fused_tail()


def _fc(op: _Op) -> List[float]:
    x, y = op.node["inputs"][0], op.node["outputs"][0]
    in_c, filters = op.shape(x)[-1], op.shape(y)[-1]
    batch = int(op.size(x) // max(1, in_c))
    return ([in_c, filters, in_c * filters + filters,
             2.0 * batch * in_c * filters, kind_cost(op.param("act"))]
            + op.fused_tail())


def _mean(op: _Op) -> List[float]:
    x = op.node["inputs"][0]
    _, ih, iw, ic = op.nhwc(x)
    return [ih, iw, ic, op.param("kernel_h", ih), op.param("kernel_w", iw),
            op.size(x), float(op.size(x))]


def _concat_split(op: _Op) -> List[float]:
    _, ih, iw, ic = op.nhwc(op.node["inputs"][0])
    outs = op.node["outputs"]
    return [ih, iw, ic, 1, 1, sum(op.shape(t)[-1] for t in outs),
            sum(op.size(t) for t in op.node["inputs"]),
            sum(op.size(t) for t in outs)]


def _pool(op: _Op) -> List[float]:
    x, y = op.node["inputs"][0], op.node["outputs"][0]
    _, ih, iw, ic = op.nhwc(x)
    _, oh, ow, _ = op.nhwc(y)
    kh, kw = op.param("kernel_h", 1), op.param("kernel_w", 1)
    return [ih, iw, ic, oh, ow, op.param("stride", 1), kh, kw, op.size(x),
            op.size(y), float(op.size(y)) * kh * kw]


def _resize(op: _Op) -> List[float]:
    x, y = op.node["inputs"][0], op.node["outputs"][0]
    _, ih, iw, ic = op.nhwc(x)
    _, oh, ow, _ = op.nhwc(y)
    return [ih, iw, ic, oh, ow, float(oh) / float(max(1, ih)), op.size(x),
            op.size(y)]


def _pad(op: _Op) -> List[float]:
    x, y = op.node["inputs"][0], op.node["outputs"][0]
    _, ih, iw, ic = op.nhwc(x)
    _, oh, ow, _ = op.nhwc(y)
    return [ih, iw, ic, oh, ow, op.size(y) - op.size(x), op.size(y)]


def _elementwise(op: _Op) -> List[float]:
    x = op.node["inputs"][0]
    _, ih, iw, ic = op.nhwc(x)
    return [ih, iw, ic, op.size(x), kind_cost(op.param("ew_kind", "add")),
            float(op.param("n_inputs", 1))]


def _activation(op: _Op) -> List[float]:
    x = op.node["inputs"][0]
    _, ih, iw, ic = op.nhwc(x)
    return [ih, iw, ic, op.size(x), kind_cost(op.param("act", "relu"))]


FEATURES = {
    "conv2d": lambda op: _conv(op, False),
    "winograd_conv2d": lambda op: _conv(op, False),
    "dwconv2d": lambda op: _conv(op, False),
    "grouped_conv2d": lambda op: _conv(op, True),
    "fully_connected": _fc,
    "mean": _mean,
    "concat": _concat_split,
    "split": _concat_split,
    "channel_shuffle": _concat_split,
    "pool_avg": _pool,
    "pool_max": _pool,
    "resize": _resize,
    "pad": _pad,
    "elementwise": _elementwise,
    "activation": _activation,
}


class ReferenceBank:
    """One saved bank, as node tables per op type."""

    def __init__(self, bank_json: Dict[str, Any]):
        self.overhead = float(bank_json["overhead"])
        self.per_kernel = float(bank_json["overhead_per_kernel"])
        self.op_sum_scale = float(bank_json["op_sum_scale"])
        self.models: Dict[str, Dict[str, Any]] = {}
        for op_type, p in bank_json["predictors"].items():
            if p["name"] != "gbdt":
                raise ValueError(f"reference walks GBDT banks, got {p['name']}")
            trees = []
            for t in p["state"]["trees"]:
                nodes = np.array([[f, thr, l, r, v, leaf]
                                  for f, thr, l, r, v, leaf in t["nodes"]],
                                 dtype=np.float64)
                trees.append(nodes)
            self.models[op_type] = {
                "f0": float(p["state"]["f0"]),
                "lr": float(p["config"]["learning_rate"]),
                "mean": np.asarray(p["scaler"]["mean"], dtype=np.float64),
                "std": np.asarray(p["scaler"]["std"], dtype=np.float64),
                "trees": trees,
            }

    @classmethod
    def load(cls, path: str) -> "ReferenceBank":
        with open(path) as f:
            return cls(json.load(f))

    def predict(self, op_type: str, x: np.ndarray,
                precision: str = "float64") -> np.ndarray:
        """Per-row prediction of ``op_type``'s model, clamped at 0."""
        m = self.models.get(op_type)
        if m is None:
            return np.zeros(len(x))
        out = np.full(len(x), m["f0"])
        for nodes in m["trees"]:
            out += m["lr"] * _walk(nodes, x, m["mean"], m["std"], precision)
        return np.maximum(out, 0.0)


def _bf16(a: np.ndarray) -> np.ndarray:
    return np.asarray(a, dtype=np.float64).astype(ml_dtypes.bfloat16) \
        .astype(np.float64)


def _walk(nodes: np.ndarray, x: np.ndarray, mean: np.ndarray,
          std: np.ndarray, precision: str) -> np.ndarray:
    """Leaf value of one tree for every row of raw features ``x``."""
    feat = nodes[:, 0].astype(np.int64)
    thr = nodes[:, 1]
    left = nodes[:, 2].astype(np.int64)
    right = nodes[:, 3].astype(np.int64)
    value = nodes[:, 4]
    leaf = nodes[:, 5] > 0
    fs = np.maximum(feat, 0)
    if precision == "float64":
        xs = (x - mean) / std
        cut = thr
    elif precision == "bfloat16":
        xs = _bf16(x)
        cut = _bf16(thr * std[fs] + mean[fs])
        value = _bf16(value)
    else:
        raise ValueError(f"unknown precision {precision!r}")
    nid = np.zeros(len(x), dtype=np.int64)
    rows = np.arange(len(x))
    for _ in range(len(nodes)):
        active = ~leaf[nid]
        if not active.any():
            break
        f = fs[nid]
        go_left = xs[rows, f] <= cut[nid]
        nid = np.where(active, np.where(go_left, left[nid], right[nid]), nid)
    return value[nid]


def predict_graphs(bank: ReferenceBank, graphs: Sequence[Dict[str, Any]],
                   precision: str = "float64") -> List[Dict[str, Any]]:
    """Reference report of each graph (JSON form): fingerprint, kernel
    count, per-op (type, seconds) in node order, and e2e seconds."""
    rows: Dict[str, List[List[float]]] = {}
    where: Dict[str, List[Tuple[int, int]]] = {}
    for gi, g in enumerate(graphs):
        for k, node in enumerate(g["nodes"]):
            t = node["op_type"]
            rows.setdefault(t, []).append(FEATURES[t](_Op(g, node)))
            where.setdefault(t, []).append((gi, k))
    per_op = [[0.0] * len(g["nodes"]) for g in graphs]
    for t, xs in rows.items():
        preds = bank.predict(t, np.asarray(xs, dtype=np.float64), precision)
        for (gi, k), p in zip(where[t], preds):
            per_op[gi][k] = float(p)
    out = []
    for g, ops in zip(graphs, per_op):
        n = len(g["nodes"])
        total = bank.overhead + bank.per_kernel * n + \
            bank.op_sum_scale * sum(ops)
        out.append({"fingerprint": fingerprint(g), "num_kernels": n,
                    "per_op": [(nd["op_type"], v)
                               for nd, v in zip(g["nodes"], ops)],
                    "e2e_s": float(total)})
    return out
