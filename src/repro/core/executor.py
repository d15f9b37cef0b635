"""IR → executable JAX callables (the profiling substrate).

Three execution modes mirror the paper's deployment modes:

  * ``op_by_op``     — each op is a separately jitted callable dispatched
                       sequentially (TFLite CPU interpreter semantics;
                       python dispatch overhead = the paper's T_overhead).
  * ``fused_groups`` — ops grouped by the Alg. C.1 fusion simulator; one
                       jitted callable per group (GPU-delegate semantics;
                       group count == kernel count).
  * ``whole_jit``    — entire graph in one XLA executable (upper bound).

Weights are deterministic per-op (seeded from the op signature) and are
closed over (XLA embeds them as constants — the analogue of TFLite
packing weights in the model file, which also lets Winograd weight
transforms be pre-computed offline, as TFLite does).
"""
from __future__ import annotations

import hashlib
from functools import partial
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from repro.core.fusion import FusionGroup, fuse_graph
from repro.core.ir import OpGraph, OpNode, op_signature

Array = Any

# ---------------------------------------------------------------------------
# Deterministic weight/input generation
# ---------------------------------------------------------------------------

def _seed_from(sig: str, tag: str) -> int:
    return int(hashlib.sha256(f"{sig}:{tag}".encode()).hexdigest()[:8], 16)


def _weight_seed(node: OpNode, shape: Sequence[int], tag: str) -> int:
    """Stable across fusion/selection rewrites: depends only on op identity
    and weight shape, so e.g. winograd_conv2d(op) == conv2d(op) numerically."""
    return _seed_from(f"op{node.op_id}:{tuple(shape)}", tag)


def make_array(shape: Sequence[int], dtype: str, seed: int, scale: float = 0.1) -> np.ndarray:
    rng = np.random.default_rng(seed)
    if np.dtype(dtype).kind in "iu":
        return rng.integers(-64, 64, size=shape, dtype=dtype)
    return (rng.standard_normal(shape) * scale).astype(dtype)


# ---------------------------------------------------------------------------
# Winograd F(2x2, 3x3) — pure-jnp implementation (also the Pallas oracle)
# ---------------------------------------------------------------------------

_B_T = np.array([[1, 0, -1, 0],
                 [0, 1, 1, 0],
                 [0, -1, 1, 0],
                 [0, 1, 0, -1]], dtype=np.float32)
_G = np.array([[1, 0, 0],
               [0.5, 0.5, 0.5],
               [0.5, -0.5, 0.5],
               [0, 0, 1]], dtype=np.float32)
_A_T = np.array([[1, 1, 1, 0],
                 [0, 1, -1, -1]], dtype=np.float32)


def winograd_transform_weights(w: Array) -> Array:
    """(3,3,C,K) → (4,4,C,K): U = G g G^T (precomputed offline, as TFLite)."""
    return jnp.einsum("ij,jkcq,lk->ilcq", _G, w, _G)


def winograd_conv2d(x: Array, u: Array, out_c: int) -> Array:
    """Winograd F(2x2,3x3) convolution, stride 1, SAME padding.

    x: (B,H,W,C); u: pre-transformed weights (4,4,C,K).  H,W assumed even.
    """
    b, h, w, c = x.shape
    nh, nw = (h + 1) // 2, (w + 1) // 2
    xp = jnp.pad(x, ((0, 0), (1, 2 * nh - h + 1), (1, 2 * nw - w + 1), (0, 0)))
    # Extract 4x4 tiles with stride 2: (B, nh, nw, 4, 4, C)
    tiles = jnp.stack(
        [xp[:, i : i + 2 * nh : 2, :, :] for i in range(4)], axis=3
    )  # (B, nh, W', 4, C)
    tiles = jnp.stack(
        [tiles[:, :, j : j + 2 * nw : 2, :, :] for j in range(4)], axis=4
    )  # (B, nh, nw, 4, 4, C)
    v = jnp.einsum("ij,bxyjkc,lk->bxyilc", _B_T, tiles, _B_T)
    m = jnp.einsum("bxyijc,ijck->bxyijk", v, u)
    y = jnp.einsum("ij,bxyjkq,lk->bxyilq", _A_T, m, _A_T)  # (B,nh,nw,2,2,K)
    y = y.transpose(0, 1, 3, 2, 4, 5).reshape(b, 2 * nh, 2 * nw, out_c)
    return y[:, :h, :w, :]


# ---------------------------------------------------------------------------
# Per-op kernels (float path)
# ---------------------------------------------------------------------------

_ACTS: Dict[str, Callable[[Array], Array]] = {
    "relu": jax.nn.relu,
    "relu6": lambda x: jnp.clip(x, 0, 6),
    "hswish": jax.nn.hard_swish,
    "swish": jax.nn.swish,
    "sigmoid": jax.nn.sigmoid,
    "gelu": jax.nn.gelu,
    "tanh": jnp.tanh,
    "identity": lambda x: x,
}

_EW_BINOPS: Dict[str, Callable[[Array, Array], Array]] = {
    "add": jnp.add, "sub": jnp.subtract, "mul": jnp.multiply,
    "div": jnp.divide, "maximum": jnp.maximum, "minimum": jnp.minimum,
    "pow": jnp.power, "equal": lambda a, b: (a == b).astype(a.dtype),
    "greater": lambda a, b: (a > b).astype(a.dtype),
    "less": lambda a, b: (a < b).astype(a.dtype),
}
# Domain-safe variants: split-block branches apply these to raw
# activations (paper §4.3.2), so sqrt/log guard their domain and exp is
# clipped — identical op cost, well-defined numerics.
_EW_UNOPS: Dict[str, Callable[[Array], Array]] = {
    "exp": lambda x: jnp.exp(jnp.clip(x, -30.0, 30.0)),
    "log": lambda x: jnp.log(jnp.abs(x) + 1e-3),
    "sqrt": lambda x: jnp.sqrt(jnp.abs(x)),
    "square": jnp.square,
    "abs": jnp.abs, "neg": jnp.negative, "copy": lambda x: x,
}


def _conv_weights(node: OpNode, graph: OpGraph, dtype: str = "float32") -> Tuple[np.ndarray, np.ndarray]:
    in_c = graph.tensor(node.inputs[0]).shape[-1]
    out_c = node.param("out_c") or graph.tensor(node.outputs[0]).shape[-1]
    kh, kw = node.param("kernel_h", 1), node.param("kernel_w", 1)
    groups = node.param("groups", 1)
    if node.op_type == "dwconv2d":
        groups = in_c
    wshape = (kh, kw, in_c // groups, out_c)
    w = make_array(wshape, dtype, _weight_seed(node, wshape, "w"))
    b = make_array((out_c,), dtype, _weight_seed(node, wshape, "b"))
    return w, b


def _conv_call(x: Array, w: Array, b: Array, stride: int, groups: int,
               act: Optional[str], padding: str = "SAME") -> Array:
    y = lax.conv_general_dilated(
        x, w,
        window_strides=(stride, stride),
        padding=padding,
        dimension_numbers=("NHWC", "HWIO", "NHWC"),
        feature_group_count=groups,
    )
    y = y + b
    if act:
        y = _ACTS[act](y)
    return y


def _apply_fused_tail(node: OpNode, y: Array, extras: List[Array]) -> Array:
    """Apply the element-wise ops merged into this kernel by Alg. C.1.

    Binary fused ops consume their true second operand from ``extras``
    (appended to node.inputs by the fusion pass, in merge order), so
    fused execution is numerically identical to unfused execution.
    Kinds marked ``@self`` had a duplicate reference to the producer's
    output dropped at merge time (diamond collapse); they read the
    kernel's base output instead — exact when the producer had no fused
    tail of its own at that merge (see fusion module docstring).
    """
    it = iter(extras)
    base = y
    for kind in node.fused:
        self_ref = kind.endswith("@self")
        if self_ref:
            kind = kind[:-5]
        if kind in _EW_UNOPS:
            y = _EW_UNOPS[kind](y)
        elif kind in _EW_BINOPS:
            rhs = base if self_ref else next(it, None)
            y = _EW_BINOPS[kind](y, y * 0.5 if rhs is None else rhs)
        elif kind in _ACTS:
            y = _ACTS[kind](y)
        elif kind in ("activation", "elementwise_lm"):
            y = _ACTS["relu"](y)
    return y


def build_op_fn(graph: OpGraph, node: OpNode) -> Tuple[Callable, List[int]]:
    """Return (fn, input tensor ids) for one op.

    ``fn`` takes *all* of ``node.inputs`` in order: the first
    ``params['n_inputs']`` feed the base op; the rest are operands of
    fused element-wise tails (paper Alg. C.1 merges rewire them here).
    """
    t = node.op_type
    p = node.params_dict
    n_base = p.get("n_inputs", 1)
    tail = partial(_apply_fused_tail, node)

    if t in ("conv2d", "grouped_conv2d"):
        w, b = _conv_weights(node, graph)
        stride = p.get("stride", 1)
        groups = p.get("groups", 1)
        act = p.get("act")
        padding = p.get("padding", "SAME")
        if t == "grouped_conv2d" and p.get("naive_split"):
            # Naive 3-stage grouped conv (split/conv-per-group/concat) —
            # the paper's baseline in Fig. 9.
            ws = [jnp.asarray(wi) for wi in np.split(w, groups, axis=3)]

            def fn(*xs):
                parts = jnp.split(xs[0], groups, axis=-1)
                ys = [
                    _conv_call(xi, wi, 0.0, stride, 1, None)
                    for xi, wi in zip(parts, ws)
                ]
                y = jnp.concatenate(ys, axis=-1) + b
                if act:
                    y = _ACTS[act](y)
                return tail(y, list(xs[n_base:]))
            return fn, list(node.inputs)

        def fn(*xs):
            return tail(_conv_call(xs[0], w, b, stride, groups, act, padding), list(xs[n_base:]))
        return fn, list(node.inputs)

    if t == "dwconv2d":
        w, b = _conv_weights(node, graph)
        stride, act = p.get("stride", 1), p.get("act")
        padding = p.get("padding", "SAME")
        in_c = graph.tensor(node.inputs[0]).shape[-1]

        def fn(*xs):
            return tail(_conv_call(xs[0], w, b, stride, in_c, act, padding), list(xs[n_base:]))
        return fn, list(node.inputs)

    if t == "winograd_conv2d":
        w, b = _conv_weights(node, graph)
        out_c = graph.tensor(node.outputs[0]).shape[-1]
        act = p.get("act")
        u = np.asarray(winograd_transform_weights(jnp.asarray(w)))  # offline

        def fn(*xs):
            y = winograd_conv2d(xs[0], u, out_c) + b
            if act:
                y = _ACTS[act](y)
            return tail(y, list(xs[n_base:]))
        return fn, list(node.inputs)

    if t == "fully_connected":
        in_c = graph.tensor(node.inputs[0]).shape[-1]
        out_c = graph.tensor(node.outputs[0]).shape[-1]
        w = make_array((in_c, out_c), "float32", _weight_seed(node, (in_c, out_c), "w"))
        b = make_array((out_c,), "float32", _weight_seed(node, (in_c, out_c), "b"))
        act = p.get("act")
        out_shape = graph.tensor(node.outputs[0]).shape

        def fn(*xs):
            y = xs[0].reshape(-1, in_c) @ w + b
            if act:
                y = _ACTS[act](y)
            return tail(y.reshape(out_shape), list(xs[n_base:]))
        return fn, list(node.inputs)

    if t == "mean":
        keep = p.get("keepdims", False)

        def fn(*xs):
            return tail(jnp.mean(xs[0], axis=(1, 2), keepdims=keep), list(xs[n_base:]))
        return fn, list(node.inputs)

    if t in ("pool_avg", "pool_max"):
        k = (p.get("kernel_h", 1), p.get("kernel_w", 1))
        s = p.get("stride", 1)

        def fn(*xs):
            init = -jnp.inf if t == "pool_max" else 0.0
            red = lax.max if t == "pool_max" else lax.add
            y = lax.reduce_window(
                xs[0], init, red,
                window_dimensions=(1, k[0], k[1], 1),
                window_strides=(1, s, s, 1),
                padding="SAME",
            )
            if t == "pool_avg":
                y = y / (k[0] * k[1])
            return tail(y, list(xs[n_base:]))
        return fn, list(node.inputs)

    if t == "concat":
        axis = p.get("axis", -1)

        def fn(*xs):
            return tail(jnp.concatenate(xs[:n_base], axis=axis), list(xs[n_base:]))
        return fn, list(node.inputs)

    if t == "split":
        n = p.get("num_splits", 2)
        axis = p.get("axis", -1)

        def fn(*xs):
            return tuple(jnp.split(xs[0], n, axis=axis))
        return fn, list(node.inputs)

    if t == "pad":
        pads = p.get("paddings", ((0, 0), (1, 1), (1, 1), (0, 0)))
        pads = tuple(tuple(q) for q in pads)

        def fn(*xs):
            return tail(jnp.pad(xs[0], pads), list(xs[n_base:]))
        return fn, list(node.inputs)

    if t == "channel_shuffle":
        g = p.get("groups", 2)

        def fn(*xs):
            b_, h, w_, c = xs[0].shape
            y = xs[0].reshape(b_, h, w_, g, c // g).transpose(0, 1, 2, 4, 3).reshape(b_, h, w_, c)
            return tail(y, list(xs[n_base:]))
        return fn, list(node.inputs)

    if t == "elementwise":
        kind = p.get("ew_kind", "add")
        if kind in _EW_UNOPS:
            def fn(*xs):
                return tail(_EW_UNOPS[kind](xs[0]), list(xs[n_base:]))
            return fn, list(node.inputs)
        if kind in _ACTS:
            def fn(*xs):
                return tail(_ACTS[kind](xs[0]), list(xs[n_base:]))
            return fn, list(node.inputs)
        if n_base >= 2:
            def fn(*xs):
                return tail(_EW_BINOPS[kind](xs[0], xs[1]), list(xs[n_base:]))
            return fn, list(node.inputs)

        def fn(*xs):
            return tail(_EW_BINOPS[kind](xs[0], xs[0]), list(xs[n_base:]))
        return fn, list(node.inputs)

    if t == "activation":
        act = p.get("act", "relu")

        def fn(*xs):
            return tail(_ACTS[act](xs[0]), list(xs[n_base:]))
        return fn, list(node.inputs)

    if t == "resize":
        out_shape = graph.tensor(node.outputs[0]).shape
        method = p.get("mode", "nearest")

        def fn(*xs):
            y = jax.image.resize(xs[0], (xs[0].shape[0],) + tuple(out_shape[1:]),
                                 method=method)
            return tail(y, list(xs[n_base:]))
        return fn, list(node.inputs)

    raise NotImplementedError(f"executor: op type {t!r} (conv-space executor)")


# ---------------------------------------------------------------------------
# Graph executors
# ---------------------------------------------------------------------------

class JitOp:
    """`jax.jit` of one op callable, with its weights passed as arguments.

    Op callables close over their weights as numpy arrays, which
    `jax.jit` would embed as constants in every compiled program: at
    224×224 that puts ~110 MiB of weights into the executables of 8 NAS
    graphs plus MobileNetV2, and into the persistent compilation cache
    that stores them.  On the first call the closed-over
    arrays are lifted out of the traced program and uploaded once; the
    compiled program takes them as inputs, as a deployed model keeps
    its weights resident in device memory.  ``__wrapped__`` is the
    plain callable, for callers that trace the op inside a larger
    program (``whole_jit``).
    """

    def __init__(self, fn: Callable):
        self.__wrapped__ = fn
        self._run: Optional[Callable] = None

    def __call__(self, *xs: Array) -> Any:
        run = self._run
        if run is None:
            closed, out_shape = jax.make_jaxpr(
                self.__wrapped__, return_shape=True)(*xs)
            consts = [jax.device_put(np.asarray(c)) for c in closed.consts]
            tree = jax.tree.structure(out_shape)

            @jax.jit
            def op_program(weights, *args):
                return jax.tree.unflatten(
                    tree, jax.core.eval_jaxpr(closed.jaxpr, weights, *args))

            run = self._run = partial(op_program, consts)
        return run(*xs)


class GraphExecutor:
    """Execute an OpGraph on JAX's default device in one of three modes.

    ``dtype='int8'`` uses the integer-arithmetic path (repro.quant).
    ``fn_cache`` (optional, signature-keyed) shares compiled per-op
    callables across executors — valid for *timing* (latency depends on
    the op config, not its weights), not for numerics.
    """

    def __init__(self, graph: OpGraph, mode: str = "op_by_op",
                 dtype: str = "float32",
                 fn_cache: Optional[Dict[str, Callable]] = None):
        assert mode in ("op_by_op", "fused_groups", "whole_jit")
        assert dtype in ("float32", "int8")
        self.graph = graph
        self.mode = mode
        self.dtype = dtype
        self.fn_cache = fn_cache
        self._build()

    def _builder(self):
        if self.dtype == "int8":
            from repro.quant.int8 import build_quant_op_fn
            return build_quant_op_fn
        return build_op_fn

    def _build(self) -> None:
        g = self.graph
        if self.mode == "fused_groups":
            _, g = fuse_graph(self.graph)
        self.exec_graph = g
        build = self._builder()
        self.op_fns: List[Tuple[OpNode, Callable, List[int]]] = []
        for node in g.nodes:
            if self.fn_cache is not None:
                sig = self.dtype + ":" + op_signature(g, node)
                jfn = self.fn_cache.get(sig)
                if jfn is None:
                    fn, in_ids = build(g, node)
                    jfn = JitOp(fn)
                    self.fn_cache[sig] = jfn
                else:
                    in_ids = list(node.inputs)
                self.op_fns.append((node, jfn, in_ids))
            else:
                fn, in_ids = build(g, node)
                self.op_fns.append((node, JitOp(fn), in_ids))

        if self.mode == "whole_jit":
            def whole(*inputs):
                env: Dict[int, Array] = dict(zip(g.input_ids, inputs))
                for node, fn, in_ids in self.op_fns:
                    outs = fn.__wrapped__(*[env[t] for t in in_ids])
                    if not isinstance(outs, tuple):
                        outs = (outs,)
                    for tid, o in zip(node.outputs, outs):
                        env[tid] = o
                return tuple(env[t] for t in g.output_ids)
            self.whole_fn = jax.jit(whole)

    def example_inputs(self, seed: int = 0) -> List[Array]:
        dtype = "int8" if self.dtype == "int8" else None
        return [
            jnp.asarray(make_array(self.exec_graph.tensor(t).shape,
                                   dtype or self.exec_graph.tensor(t).dtype,
                                   seed + i, scale=1.0))
            for i, t in enumerate(self.exec_graph.input_ids)
        ]

    def __call__(self, *inputs: Array, sync_per_op: bool = False) -> Tuple[Array, ...]:
        """Run the graph.

        ``sync_per_op=True`` blocks after every op — TFLite-CPU-interpreter
        semantics (ops strictly sequential).  False leaves XLA's async
        dispatch free to overlap — the GPU-command-queue analogue.
        """
        g = self.exec_graph
        if self.mode == "whole_jit":
            return self.whole_fn(*inputs)
        env: Dict[int, Array] = dict(zip(g.input_ids, inputs))
        for node, fn, in_ids in self.op_fns:
            outs = fn(*[env[t] for t in in_ids])
            if not isinstance(outs, tuple):
                outs = (outs,)
            if sync_per_op:
                outs[0].block_until_ready()
            for tid, o in zip(node.outputs, outs):
                env[tid] = o
        return tuple(env[t] for t in g.output_ids)

    def kernel_count(self) -> int:
        return len(self.op_fns) if self.mode != "whole_jit" else 1
