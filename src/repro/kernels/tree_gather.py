"""Device-resident tree-ensemble gather backends (`jax.jit` + mesh sharding).

Batched tree traversal is a pure gather workload: every (row × tree)
slot holds a node id, and one step gathers (feature, threshold, child)
for all slots at once.  Because leaves self-loop (`left == right ==
self` in `FlatEnsemble`), the update is idempotent, so a fixed-depth
`lax.fori_loop` of ``max_depth`` iterations needs no active mask — rows
that reached a leaf simply stay put.  That keeps the whole traversal one
XLA computation (no host sync per level), which wins once
rows × trees is large; the numpy mask loop wins on small batches.

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
from functools import partial
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from repro.launch.mesh import flush_mesh

# Lifetime counters (survive bank invalidation — `DeviceBank` instances
# die with their FlatEnsemble, these do not).  `LatencyService.stats()`
# reports both views: what is resident now and what was ever uploaded.
_COUNTERS = {"banks_built": 0, "bank_bytes": 0, "inputs_staged": 0,
             "input_bytes": 0}
_COUNTERS_LOCK = threading.Lock()

# Flushes below this many rows skip mesh sharding: the all-gather +
# dispatch overhead beats the per-device win on small batches.
SHARD_MIN_ROWS = 1024


def residency_counters() -> Dict[str, int]:
    """Process-lifetime upload totals (includes invalidated banks)."""
    with _COUNTERS_LOCK:
        return dict(_COUNTERS)


def _count(**deltas: int) -> None:
    with _COUNTERS_LOCK:
        for k, v in deltas.items():
            _COUNTERS[k] += v


def _traverse_core(feature, threshold, left, right, value, roots, x, *,
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


def _fused_core(feature, raw_threshold, left, right, value, roots,
                scale, bias, x, *, depth, kind):
    # Raw features against raw-space thresholds (`raw_thresholds`): the
    # standardization is folded into the bank, not computed in float32.
    vals = _traverse_core(feature, raw_threshold, left, right, value,
                          roots, x, depth=depth)
    red = jnp.sum(vals, axis=1) if kind == "sum" else jnp.mean(vals, axis=1)
    return jnp.maximum(bias + scale * red, 0.0)           # Predictor.predict clamp


_traverse = jax.jit(_traverse_core, static_argnames=("depth",))
_fused = jax.jit(_fused_core, static_argnames=("depth", "kind"))


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
        # Leaves carry feature = -1; clamp to 0 so the take_along_axis
        # gather stays in-bounds (self-looped slots ignore the compare).
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
                                  partial(_traverse_core, depth=self.depth),
                                  out_rank2=True)
            return fn(*self.bank_args, xd)
        return _traverse(*self.bank_args, xd, depth=self.depth)

    def fused(self, raw_threshold, scale, bias, xd, kind: str) -> Any:
        """traverse raw rows → reduce → clamp, one device program."""
        args = (self.feature, raw_threshold, self.left, self.right,
                self.value, self.roots, scale, bias, xd)
        if self.mesh is not None and _row_sharded(xd):
            fn = self._sharded_fn(("fused", self.depth, kind),
                                  partial(_fused_core, depth=self.depth,
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
    """(n_rows, n_trees) leaf values via the jit'd gather loop.

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


def fused_predict(flat, raw_threshold, reduction: Tuple,
                  x: np.ndarray) -> np.ndarray:
    """Whole per-op-type predict on device: raw f32 features in,
    clamped latencies out.

    ``reduction`` is the model's ``(kind, scale, bias)`` — GBDT is
    ``("sum", learning_rate, f0)``, RF is ``("mean", 1.0, 0.0)`` — so
    traversal, the stage/tree reduction, and the ≥0 clamp all run in
    one device program instead of bouncing a float64 (rows × trees)
    matrix back through the host.  ``raw_threshold`` comes from
    `raw_thresholds` for the model's scaler.
    """
    kind, scale, bias = reduction
    db = flat.device_bank()
    n = x.shape[0]
    out = db.fused(raw_threshold, jnp.float32(scale), jnp.float32(bias),
                   db.stage_input(x), kind)[:n]
    return np.asarray(out, dtype=np.float64)
