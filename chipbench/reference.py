"""Plain float64 reference of a served prediction (paper §4.2).

Independent of the program: it reads a graph in its JSON wire form and
the bank in the JSON the hub saves, computes each op's features with
the configuration's feature table (``FEATURES`` of
``chipbench/reference_<name>.py``, written from the definitions of the
op types its graphs bring), walks every tree node by node in float64
on standardized features, and composes

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
from typing import Any, Callable, Dict, List, Mapping, Sequence, Tuple

import ml_dtypes
import numpy as np


def fingerprint(graph_json: Dict[str, Any]) -> str:
    blob = json.dumps(graph_json, sort_keys=True).encode()
    return hashlib.sha256(blob).hexdigest()[:16]


class Op:
    """One node of a graph's JSON form, as a feature function reads it."""

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


FeatureTable = Mapping[str, Callable[[Op], List[float]]]


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
                   features: FeatureTable,
                   precision: str = "float64") -> List[Dict[str, Any]]:
    """Reference report of each graph (JSON form): fingerprint, kernel
    count, per-op (type, seconds) in node order, and e2e seconds;
    ``features`` maps each op type to its feature function."""
    rows: Dict[str, List[List[float]]] = {}
    where: Dict[str, List[Tuple[int, int]]] = {}
    for gi, g in enumerate(graphs):
        for k, node in enumerate(g["nodes"]):
            t = node["op_type"]
            rows.setdefault(t, []).append(features[t](Op(g, node)))
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
