"""Device-resident tree-ensemble traversal (`jax.jit` + mesh sharding).

Every (row × tree) slot is routed from its tree's root to a leaf by the
float32 test ``x[feature] <= threshold`` (left) at each node.  Leaves
self-loop (`left == right == self` in `FlatEnsemble`), so a tree of any
shape behaves as a complete tree of the bank's static depth ``D``: a
leaf above depth ``D`` fills its subtree with itself.  Two forms of the
same traversal compute bit-identical leaves, chosen by ``D``:

* dense (``D <= DENSE_MAX_DEPTH``): the completed tree's node ids are
  built level by level from the roots, ``(trees, 2**l)`` at level ``l``,
  by gathers over the small bank arrays that do not depend on the rows.
  Every node's decision for every row is then computed at once, its
  feature value picked from the row's features by compare-and-select,
  and the rows walk the levels by index arithmetic:
  ``i_(l+1) = 2·i_l + bit_l``, with ``bit_l`` selected from the level's
  ``2**l`` decisions by compare-and-select against ``i_l``; the leaf
  value is a select over the ``2**D`` completed leaves.  No gather
  touches the (rows × trees) slot array, which is the access pattern a
  TPU serves worst, and there is no loop.
* loop (deeper banks): a fixed-depth `lax.fori_loop` that gathers
  (feature, threshold, child) for all slots at once per level.  The
  dense form's work and intermediates grow as ``2**D`` a slot, the
  loop's as ``D``, so deep banks (a random forest's default depth 14)
  keep it.

Either way the whole traversal is one XLA computation (no host sync per
level), which wins once rows × trees is large; the numpy mask loop wins
on small batches.

Residency (`DeviceBank`): the flattened struct-of-arrays bank is
uploaded to the accelerator ONCE per `FlatEnsemble` and reused across
every subsequent flush — the bank arrays live on `flat._device_bank`
until the ensemble itself is invalidated (retrain / bank swap), so a
serving process pays host→device transfer of the trees exactly once.
Inputs are staged through the same layer as float32 (half the bytes of
a float64 bounce).  They are not donated: no output of the traversal
has an input's shape, so XLA could not reuse the buffer.

Sharding: when the process sees more than one accelerator, the bank is
built against a 1-axis ``("rows",)`` mesh (`repro.launch.mesh.flush_mesh`)
— bank arrays replicated, flush rows sharded via `jax.shard_map`, results
reassembled deterministically in row order (rows are padded to a device
multiple and the pad sliced off, so reassembly is a plain row-major
gather).

Precision: runs at jax's default precision (float32 unless x64 is
enabled), so predictions can differ from the float64 numpy backend in
the last ulps — and near-tie thresholds can route differently.  The
numpy backend stays the bit-exact default; the device tier is opt-in
(``backend="jax"`` / ``"auto"``) for large-batch NAS scoring.
"""
from __future__ import annotations

import threading
from functools import partial, wraps
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from repro.launch.mesh import flush_mesh
from repro.obs.tracing import Tracer

# Lifetime counters (survive bank invalidation — `DeviceBank` instances
# die with their FlatEnsemble, these do not).  `LatencyService.stats()`
# reports both views: what is resident now and what was ever uploaded.
# ``programs_traced`` counts traversal programs traced for a new shape
# or mesh, each of which is then compiled or fetched from the
# persistent cache; ``dense_programs_traced`` those of them that took
# the dense form.
_COUNTERS = {"banks_built": 0, "bank_bytes": 0, "inputs_staged": 0,
             "input_bytes": 0, "programs_traced": 0,
             "dense_programs_traced": 0}
_COUNTERS_LOCK = threading.Lock()

# Flushes below this many rows skip mesh sharding: the all-gather +
# dispatch overhead beats the per-device win on small batches.
SHARD_MIN_ROWS = 1024

# Banks up to this static depth traverse in the dense form, deeper ones
# in the gather loop (module docstring).  The dense form's selects,
# intermediates and compile time grow as 2**depth.  On a TPU v5e it
# ran 40-500x faster than the loop at depths 4 to 8 (150 trees, 2,048
# and 8,192 rows) and compiled in about the loop's time through depth
# 6; at depth 8 it compiled several times slower, which a program
# compiled per row count pays (PERF.md, section 6).
DENSE_MAX_DEPTH = 6


def residency_counters() -> Dict[str, int]:
    """Process-lifetime upload totals (includes invalidated banks)."""
    with _COUNTERS_LOCK:
        return dict(_COUNTERS)


def _count(**deltas: int) -> None:
    with _COUNTERS_LOCK:
        for k, v in deltas.items():
            _COUNTERS[k] += v


def traversal_form(depth: int) -> str:
    """``"dense"`` or ``"loop"``: the form a bank of static ``depth``
    traverses in."""
    return "dense" if depth <= DENSE_MAX_DEPTH else "loop"


def _traverse_loop(feature, threshold, left, right, value, roots, x, *,
                   depth):
    # Level 0 is peeled out of the loop: every row starts at the same
    # roots, so it is a plain column gather, and the loop carry it
    # yields is built from ``x`` — under `shard_map` that makes the
    # carry vary over the rows axis like the loop's output does.
    first = jnp.where(x[:, feature[roots]] <= threshold[roots],
                      left[roots], right[roots])         # (rows, trees)

    def body(_, nid):
        f = feature[nid]                                  # gather per slot
        thr = threshold[nid]
        xv = jnp.take_along_axis(x, f, axis=1)            # x[row, f[row, tree]]
        return jnp.where(xv <= thr, left[nid], right[nid])

    nid = lax.fori_loop(1, depth, body, first)
    return value[nid]


def _select(idx, columns):
    """``columns[idx]`` elementwise by compare-and-select: ``columns`` is
    a static list of arrays that broadcast against ``idx``, and every
    ``idx`` lies in ``range(len(columns))``.  Selecting copies bits, so
    the result is exact in any dtype."""
    out = columns[0]
    for k in range(1, len(columns)):
        out = jnp.where(idx == k, columns[k], out)
    return out


def _traverse_dense(feature, threshold, left, right, value, roots, x, *,
                    depth):
    # The completed tree's node ids, level by level: (trees, 2**l) at
    # level l, children interleaved, so position j's children sit at 2j
    # and 2j + 1 of the next level.
    n_trees = roots.shape[0]
    levels = [roots[:, None]]
    for _ in range(depth):
        ids = levels[-1]
        levels.append(jnp.stack([left[ids], right[ids]], axis=-1)
                      .reshape(n_trees, -1))
    inner = jnp.concatenate(levels[:-1], axis=1)     # (trees, 2**depth - 1)
    # Every node's decision for every row, laid out (trees, nodes, rows),
    # rows minor; a node's feature value is picked from the row's
    # features by compare-and-select (exact, unlike a matmul at the
    # TPU's default precision), not by a column gather.
    xv = _select(feature[inner][..., None],
                 [x[:, f][None, None, :] for f in range(x.shape[1])])
    goes_right = ~(xv <= threshold[inner][..., None])
    # Walk the levels: ``at`` is the position each slot stands on.
    at = 0
    for level in range(depth):
        first = 2 ** level - 1
        bit = _select(at, [goes_right[:, first + j]
                           for j in range(2 ** level)])
        at = 2 * at + bit.astype(jnp.int32)                  # (trees, rows)
    leaves = value[levels[-1]]                         # (trees, 2**depth)
    return _select(at, [leaves[:, j:j + 1]
                        for j in range(leaves.shape[1])]).T


def _traverse_core(feature, threshold, left, right, value, roots, x, *,
                   depth):
    form = (_traverse_dense if traversal_form(depth) == "dense"
            else _traverse_loop)
    return form(feature, threshold, left, right, value, roots, x,
                depth=depth)


def _fused_core(feature, raw_threshold, left, right, value, roots,
                scale, bias, x, *, depth, kind):
    # Raw features against raw-space thresholds (`raw_thresholds`): the
    # standardization is folded into the bank, not computed in float32.
    vals = _traverse_core(feature, raw_threshold, left, right, value,
                          roots, x, depth=depth)
    red = jnp.sum(vals, axis=1) if kind == "sum" else jnp.mean(vals, axis=1)
    return jnp.maximum(bias + scale * red, 0.0)           # Predictor.predict clamp


def _entry(core):
    """``core`` as a jitted program's entry point: counts one traced
    program, and whether it took the dense form, each time JAX traces
    it.  The count is a trace-time side effect, so a cached program's
    call costs nothing; it sits on the entry and not in the shared
    bodies (`_fused_core` calls `_traverse_core`).  `wraps` keeps the
    XLA module name ``jit_<core name>`` that the device-trace readers
    match."""
    @wraps(core)
    def traced(*args, depth, **kwargs):
        _count(programs_traced=1,
               dense_programs_traced=int(traversal_form(depth) == "dense"))
        return core(*args, depth=depth, **kwargs)
    return traced


_traverse_entry = _entry(_traverse_core)
_fused_entry = _entry(_fused_core)
_traverse = jax.jit(_traverse_entry, static_argnames=("depth",))
_fused = jax.jit(_fused_entry, static_argnames=("depth", "kind"))
# Stands in for the tracer of an untraced call: its spans are no-ops.
_UNTRACED = Tracer(enabled=False)


class DeviceBank:
    """One `FlatEnsemble`'s arrays resident on the accelerator.

    Built lazily by `FlatEnsemble.device_bank()` and cached on the
    ensemble, so the host→device transfer of the bank happens once per
    trained ensemble — retrain/bank-swap drops the FlatEnsemble (and
    this bank with it), which is the invalidation path.  `uploads`
    stays 1 for the bank arrays by construction; the regression test in
    tests/test_fastpath.py pins that.
    """

    __slots__ = ("n_nodes", "n_trees", "depth", "feature", "threshold",
                 "left", "right", "value", "roots", "mesh", "nbytes",
                 "uploads", "inputs_staged", "input_bytes",
                 "_fn_cache", "_lock")

    def __init__(self) -> None:
        self._fn_cache: Dict[Tuple, Any] = {}
        self._lock = threading.Lock()
        self.mesh = None
        self.uploads = 0
        self.inputs_staged = 0
        self.input_bytes = 0

    @classmethod
    def from_flat(cls, flat) -> "DeviceBank":
        db = cls()
        db.n_nodes = flat.n_nodes
        db.n_trees = flat.n_trees
        db.depth = max(1, flat.max_depth)
        db.mesh = flush_mesh()
        # Leaves carry feature = -1; clamp to 0 so every node names a
        # real column in either form (a leaf's children are itself, so
        # its compare is ignored).
        host = (np.maximum(flat.feature, 0).astype(np.int32),
                f32_thresholds(flat.threshold, 0.0, 1.0),
                flat.left.astype(np.int32),
                flat.right.astype(np.int32),
                flat.value.astype(np.float32),
                flat.roots.astype(np.int32))
        (db.feature, db.threshold, db.left, db.right, db.value,
         db.roots) = (db.place(a) for a in host)
        db.nbytes = sum(a.nbytes for a in host)
        db.uploads = 1
        _count(banks_built=1, bank_bytes=db.nbytes)
        return db

    def place(self, a: np.ndarray):
        """Host array → device, replicated over the mesh when there is one."""
        if self.mesh is None:
            return jnp.asarray(a)
        return jax.device_put(a, jax.sharding.NamedSharding(
            self.mesh, jax.sharding.PartitionSpec()))

    @property
    def bank_args(self) -> Tuple:
        return (self.feature, self.threshold, self.left, self.right,
                self.value, self.roots)

    # -- input staging --------------------------------------------------------
    def stage_input(self, x: np.ndarray):
        """Host rows → committed f32 device array (row-sharded on a mesh).

        Rows are padded up to a device multiple when sharding; callers
        slice results back to ``x.shape[0]`` — padding + row-major
        gather is what makes multi-device reassembly deterministic.
        """
        x32 = np.ascontiguousarray(x, dtype=np.float32)
        mesh = self.mesh if len(x32) >= SHARD_MIN_ROWS else None
        if mesh is not None:
            ndev = mesh.devices.size
            pad = (-len(x32)) % ndev
            if pad:
                x32 = np.concatenate(
                    [x32, np.zeros((pad, x32.shape[1]), np.float32)])
            sh = jax.sharding.NamedSharding(
                mesh, jax.sharding.PartitionSpec("rows", None))
            xd = jax.device_put(x32, sh)
        else:
            xd = jnp.asarray(x32)
        with self._lock:
            self.inputs_staged += 1
            self.input_bytes += x32.nbytes
        _count(inputs_staged=1, input_bytes=x32.nbytes)
        return xd

    # -- traversal dispatch ---------------------------------------------------
    def _sharded_fn(self, key: Tuple, core, out_rank2: bool):
        """`shard_map`-wrapped jit of ``core`` over the rows axis (cached)."""
        fn = self._fn_cache.get(key)
        if fn is None:
            P = jax.sharding.PartitionSpec
            n_repl = 6 if out_rank2 else 8
            fn = jax.jit(jax.shard_map(
                core, mesh=self.mesh,
                in_specs=(P(),) * n_repl + (P("rows", None),),
                out_specs=P("rows", None) if out_rank2 else P("rows")))
            with self._lock:
                self._fn_cache.setdefault(key, fn)
            fn = self._fn_cache[key]
        return fn

    def gather_leaves(self, xd) -> Any:
        """(rows, trees) leaf values for staged rows ``xd`` (device)."""
        if self.mesh is not None and _row_sharded(xd):
            fn = self._sharded_fn(("traverse", self.depth),
                                  partial(_traverse_entry, depth=self.depth),
                                  out_rank2=True)
            return fn(*self.bank_args, xd)
        return _traverse(*self.bank_args, xd, depth=self.depth)

    def fused(self, raw_threshold, scale, bias, xd, kind: str) -> Any:
        """traverse raw rows → reduce → clamp, one device program."""
        args = (self.feature, raw_threshold, self.left, self.right,
                self.value, self.roots, scale, bias, xd)
        if self.mesh is not None and _row_sharded(xd):
            fn = self._sharded_fn(("fused", self.depth, kind),
                                  partial(_fused_entry, depth=self.depth,
                                          kind=kind),
                                  out_rank2=False)
            return fn(*args)
        return _fused(*args, depth=self.depth, kind=kind)

    # -- introspection --------------------------------------------------------
    def stats(self) -> Dict[str, Any]:
        return {"nbytes": int(self.nbytes), "n_nodes": int(self.n_nodes),
                "n_trees": int(self.n_trees), "uploads": int(self.uploads),
                "inputs_staged": int(self.inputs_staged),
                "input_bytes": int(self.input_bytes),
                "sharded": self.mesh is not None}


def _row_sharded(xd) -> bool:
    """True when ``xd`` was staged with a row sharding (mesh flush)."""
    sh = getattr(xd, "sharding", None)
    spec = getattr(sh, "spec", None)
    return bool(spec) and spec[0] == "rows"


# -- public backends ----------------------------------------------------------

def predict_trees_jax(flat, x: np.ndarray) -> np.ndarray:
    """(n_rows, n_trees) leaf values via the jit'd traversal.

    Bank arrays come from the persistent `DeviceBank` (uploaded once per
    ensemble); only the f32 input is transferred per flush.
    """
    db = flat.device_bank()
    n = x.shape[0]
    out = db.gather_leaves(db.stage_input(x))
    return np.asarray(out[:n], dtype=np.float64)


def f32_thresholds(threshold: np.ndarray, mean, std) -> np.ndarray:
    """Largest float32 ``v`` per node that the float64 host routes left.

    The host sends a row left when ``(x - mean) / std <= threshold`` in
    float64; that test is monotone in ``x``, so one float32 cut-off per
    node reproduces it exactly for every float32-representable ``x``
    (integer features below 2²⁴, which is most of them) — including a
    row that sits exactly on a split midpoint, where the host's answer
    depends on float64 rounding.  ``mean=0, std=1`` gives the cut-off
    for already-standardized rows.
    """
    def left(v):
        return (v.astype(np.float64) - mean) / std <= threshold

    inf = np.float32(np.inf)
    t = (threshold * std + mean).astype(np.float32)
    # Rounding to float32 lands within a step or two of the cut-off.
    for _ in range(8):
        up = np.nextafter(t, inf)
        go_up, go_down = left(up), ~left(t)
        if not (go_up.any() or go_down.any()):
            return t
        t = np.where(go_up, up, np.where(go_down, np.nextafter(t, -inf), t))
    raise RuntimeError("float32 thresholds did not converge")


def raw_thresholds(flat, scaler):
    """Device thresholds in raw feature units, for the fused path.

    The fused program compares raw float32 features with these
    (`f32_thresholds` of the model's scaler), so the standardization is
    folded in on the host.  Standardizing on the device instead cancels
    catastrophically in float32 for large, clustered features (FLOPs,
    bytes) and routes rows far from any threshold wrongly.
    """
    db = flat.device_bank()
    f = np.maximum(flat.feature, 0)
    raw = db.place(f32_thresholds(flat.threshold, scaler.mean[f],
                                  scaler.std[f]))
    with db._lock:
        db.nbytes += raw.nbytes
    _count(bank_bytes=raw.nbytes)
    return raw


def fused_predict(flat, raw_threshold, reduction: Tuple, x: np.ndarray,
                  tracer: Optional[Tracer] = None) -> np.ndarray:
    """Whole per-op-type predict on device: raw f32 features in,
    clamped latencies out.

    ``reduction`` is the model's ``(kind, scale, bias)`` — GBDT is
    ``("sum", learning_rate, f0)``, RF is ``("mean", 1.0, 0.0)`` — so
    traversal, the stage/tree reduction, and the ≥0 clamp all run in
    one device program instead of bouncing a float64 (rows × trees)
    matrix back through the host.  ``raw_threshold`` comes from
    `raw_thresholds` for the model's scaler.

    With an enabled ``tracer`` the call records three spans, children
    of the caller's ambient span: ``tree.stage`` (the float32 batch and
    its transfer; attrs ``rows``, ``bytes``), ``tree.dispatch`` (the
    program and the slice are enqueued; attr ``form``, ``"dense"`` or
    ``"loop"``) and ``tree.wait`` (the host blocks on the readback).
    """
    kind, scale, bias = reduction
    tracer = _UNTRACED if tracer is None else tracer
    db = flat.device_bank()
    n = x.shape[0]
    with tracer.span("tree.stage") as sp:
        xd = db.stage_input(x)
        if tracer.enabled:
            sp.set_attr("rows", n)
            sp.set_attr("bytes", int(xd.nbytes))
    with tracer.span("tree.dispatch") as sp:
        out = db.fused(raw_threshold, jnp.float32(scale), jnp.float32(bias),
                       xd, kind)[:n]
        if tracer.enabled:
            sp.set_attr("form", traversal_form(db.depth))
    with tracer.span("tree.wait"):
        return np.asarray(out, dtype=np.float64)
