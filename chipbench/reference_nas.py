"""Reference op features of the NAS configurations: paper Table 3's
definitions, with the repository's documented extensions (activation
cost tier and fused-tail features), for the conv-space op types."""
from __future__ import annotations

from typing import Any, List, Tuple

from chipbench.reference import Op

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


def nhwc(op: Op, tid: int) -> Tuple[int, int, int, int]:
    s = op.shape(tid)
    if len(s) == 4:
        return s[0], s[1], s[2], s[3]
    if len(s) == 3:
        return 1, s[0], s[1], s[2]
    if len(s) == 2:
        return s[0], 1, 1, s[1]
    raise ValueError(f"unsupported shape {s}")


def fused_tail(op: Op) -> List[float]:
    fused = op.node.get("fused", [])
    return [float(len(fused)), float(sum(kind_cost(k) for k in fused))]


def _conv(op: Op, grouped: bool) -> List[float]:
    x, y = op.node["inputs"][0], op.node["outputs"][0]
    _, ih, iw, ic = nhwc(op, x)
    _, oh, ow, oc = nhwc(op, y)
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
    return vals + [kind_cost(op.param("act"))] + fused_tail(op)


def _fc(op: Op) -> List[float]:
    x, y = op.node["inputs"][0], op.node["outputs"][0]
    in_c, filters = op.shape(x)[-1], op.shape(y)[-1]
    batch = int(op.size(x) // max(1, in_c))
    return ([in_c, filters, in_c * filters + filters,
             2.0 * batch * in_c * filters, kind_cost(op.param("act"))]
            + fused_tail(op))


def _mean(op: Op) -> List[float]:
    x = op.node["inputs"][0]
    _, ih, iw, ic = nhwc(op, x)
    return [ih, iw, ic, op.param("kernel_h", ih), op.param("kernel_w", iw),
            op.size(x), float(op.size(x))]


def _concat_split(op: Op) -> List[float]:
    _, ih, iw, ic = nhwc(op, op.node["inputs"][0])
    outs = op.node["outputs"]
    return [ih, iw, ic, 1, 1, sum(op.shape(t)[-1] for t in outs),
            sum(op.size(t) for t in op.node["inputs"]),
            sum(op.size(t) for t in outs)]


def _pool(op: Op) -> List[float]:
    x, y = op.node["inputs"][0], op.node["outputs"][0]
    _, ih, iw, ic = nhwc(op, x)
    _, oh, ow, _ = nhwc(op, y)
    kh, kw = op.param("kernel_h", 1), op.param("kernel_w", 1)
    return [ih, iw, ic, oh, ow, op.param("stride", 1), kh, kw, op.size(x),
            op.size(y), float(op.size(y)) * kh * kw]


def _resize(op: Op) -> List[float]:
    x, y = op.node["inputs"][0], op.node["outputs"][0]
    _, ih, iw, ic = nhwc(op, x)
    _, oh, ow, _ = nhwc(op, y)
    return [ih, iw, ic, oh, ow, float(oh) / float(max(1, ih)), op.size(x),
            op.size(y)]


def _pad(op: Op) -> List[float]:
    x, y = op.node["inputs"][0], op.node["outputs"][0]
    _, ih, iw, ic = nhwc(op, x)
    _, oh, ow, _ = nhwc(op, y)
    return [ih, iw, ic, oh, ow, op.size(y) - op.size(x), op.size(y)]


def _elementwise(op: Op) -> List[float]:
    x = op.node["inputs"][0]
    _, ih, iw, ic = nhwc(op, x)
    return [ih, iw, ic, op.size(x), kind_cost(op.param("ew_kind", "add")),
            float(op.param("n_inputs", 1))]


def _activation(op: Op) -> List[float]:
    x = op.node["inputs"][0]
    _, ih, iw, ic = nhwc(op, x)
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
