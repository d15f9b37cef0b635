"""The dense traversal of shallow banks against the gather loop and the
float64 host.

Banks up to `tree_gather.DENSE_MAX_DEPTH` traverse densely (level-wise
compare-and-select over the completed tree), deeper ones in the gather
loop.  Both forms must route every (row, tree) slot to the same leaf,
so their leaf values are compared bit for bit, and against the numpy
tier, whose float64 routing the float32 cut-offs reproduce exactly for
float32 rows.  Banks cover unbalanced trees (leaves above the bank's
depth), stumps, single-leaf trees and a bank of nothing but leaves
(depth clamped to 1); rows sit on and one ulp either side of every
cut-off.  The fused program is checked for both reductions, and the
row-sharded programs on four virtual CPU devices.
"""
import json
import os
import subprocess
import sys
import textwrap
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.predictors import (
    FlatEnsemble, GBDTPredictor, RandomForestPredictor,
)
from repro.kernels import tree_gather as tg
from repro.obs import Tracer

REPO_SRC = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "src")
FEATURES = 5
# Thresholds and row values share one grid, so rows land on cut-offs.
# It leaves out 0, whose ulp neighbours are subnormals, which XLA
# flushes to zero on the device in either form.
GRID = np.arange(-6, 7) * 0.5 + 0.25


def synthetic_flat(seed, trees, depth, leaf_p=0.0, leaf_trees=()):
    """A bank of random trees up to ``depth`` levels of splits; a node
    below the root turns into a leaf with probability ``leaf_p``, and
    the trees numbered in ``leaf_trees`` are a single leaf."""
    rng = np.random.default_rng(seed)
    feature, threshold, left, right, value, roots = [], [], [], [], [], []

    def node(d, split):
        j = len(feature)
        feature.append(-1)
        threshold.append(0.0)
        left.append(j)
        right.append(j)
        value.append(float(rng.normal()))
        if split and d < depth and (d == 0 or rng.random() >= leaf_p):
            feature[j] = int(rng.integers(FEATURES))
            threshold[j] = float(rng.choice(GRID))
            left[j] = node(d + 1, True)
            right[j] = node(d + 1, True)
        return j

    for t in range(trees):
        roots.append(node(0, t not in leaf_trees))
    arrays = (np.array(feature, np.int32), np.array(threshold),
              np.array(left, np.int32), np.array(right, np.int32),
              np.array(value), np.array(roots, np.int32))
    return FlatEnsemble(*arrays, max_depth=FlatEnsemble._measure_depth(
        arrays[0], arrays[2], arrays[3], arrays[5]))


BANKS = {
    "unbalanced_d4": dict(seed=1, trees=40, depth=4, leaf_p=0.3),
    "unbalanced_d6": dict(seed=2, trees=12, depth=6, leaf_p=0.35),
    "balanced_d3": dict(seed=3, trees=16, depth=3),
    "stumps": dict(seed=4, trees=30, depth=1),
    "single_leaf_tree": dict(seed=5, trees=9, depth=4, leaf_p=0.2,
                             leaf_trees=(0, 4)),
    "leaves_only": dict(seed=6, trees=4, depth=3, leaf_trees=range(4)),
}


def _rows(seed, n, cuts=None):
    """float32 rows on the grid; with ``cuts`` ((node feature, float32
    cut-off) pairs) three more rows per cut: on it and one ulp below
    and above, in that node's feature."""
    rng = np.random.default_rng(seed)
    x = rng.choice(GRID, size=(n, FEATURES)).astype(np.float32)
    if cuts is not None:
        extra = []
        for f, c in cuts:
            for v in (np.nextafter(c, np.float32(-np.inf)), c,
                      np.nextafter(c, np.float32(np.inf))):
                r = rng.choice(GRID, size=FEATURES).astype(np.float32)
                r[f] = v
                extra.append(r)
        x = np.concatenate([x, np.array(extra, np.float32).reshape(
            -1, FEATURES)])
    return x


def _cuts(flat, device_thresholds):
    inner = np.flatnonzero(flat.feature >= 0)
    return list(zip(flat.feature[inner], np.asarray(device_thresholds)[inner]))


def _forms(bank_args, x, depth):
    """(loop, dense) leaf values, each as its own program."""
    xd = jnp.asarray(x)
    return tuple(np.asarray(jax.jit(partial(core, depth=depth))(*bank_args, xd))
                 for core in (tg._traverse_loop, tg._traverse_dense))


def _bits(a):
    return np.asarray(a, np.float32).view(np.uint32)


@pytest.mark.parametrize("bank", sorted(BANKS))
def test_dense_leaves_bit_equal_loop_and_host(bank):
    flat = synthetic_flat(**BANKS[bank])
    db = flat.device_bank()
    assert tg.traversal_form(db.depth) == "dense"
    if bank == "leaves_only":
        assert flat.max_depth == 0 and db.depth == 1
    x = _rows(7, 300, _cuts(flat, db.threshold))
    loop, dense = _forms(db.bank_args, x, db.depth)
    assert dense.shape == (len(x), flat.n_trees)
    assert np.array_equal(_bits(dense), _bits(loop))
    host = flat.predict_trees(x.astype(np.float64), backend="numpy")
    assert np.array_equal(_bits(dense), _bits(host))
    # The public entry (`predict_trees_jax` → `_traverse`) is the same.
    served = flat.predict_trees(x.astype(np.float64), backend="jax")
    assert np.array_equal(_bits(served), _bits(dense))


def _fitted(kind):
    rng = np.random.default_rng(11)
    x = np.abs(rng.standard_normal((400, FEATURES))) * np.array(
        [1.0, 30.0, 2.0 ** 20, 5.0, 0.01])
    y = x @ rng.random(FEATURES) + 0.1
    if kind == "sum":
        return GBDTPredictor(n_stages=30, max_depth=4).fit(x, y), x
    return RandomForestPredictor(n_trees=8, max_depth=4).fit(x, y), x


@pytest.mark.parametrize("thresholds", ["f32_thresholds", "raw_thresholds"])
def test_rows_on_and_beside_cutoffs_route_like_host(thresholds):
    m, x = _fitted("sum")
    flat = m.flat()
    db = flat.device_bank()
    assert tg.traversal_form(db.depth) == "dense"
    if thresholds == "f32_thresholds":
        dev_thr = db.threshold              # cut-offs of standardized rows
        base = m.scaler.transform(x).astype(np.float32)
    else:
        dev_thr = tg.raw_thresholds(flat, m.scaler)
        base = x.astype(np.float32)
    rows = []
    for f, c in _cuts(flat, dev_thr):
        r = base[len(rows) % len(base)].copy()
        for v in (np.nextafter(c, np.float32(-np.inf)), c,
                  np.nextafter(c, np.float32(np.inf))):
            r[f] = v
            rows.append(r.copy())
    q = np.array(rows, np.float32)
    args = (db.feature, dev_thr, db.left, db.right, db.value, db.roots)
    loop, dense = _forms(args, q, db.depth)
    assert np.array_equal(_bits(dense), _bits(loop))
    q64 = q.astype(np.float64)
    if thresholds == "raw_thresholds":
        q64 = m.scaler.transform(q64)
    host = flat.predict_trees(q64, backend="numpy")
    assert np.array_equal(_bits(dense), _bits(host))


def _loop_fused(feature, thr, left, right, value, roots, scale, bias, x, *,
                depth, kind):
    vals = tg._traverse_loop(feature, thr, left, right, value, roots, x,
                             depth=depth)
    red = jnp.sum(vals, axis=1) if kind == "sum" else jnp.mean(vals, axis=1)
    return jnp.maximum(bias + scale * red, 0.0)


@pytest.mark.parametrize("kind", ["sum", "mean"])
def test_fused_program_matches_loop_and_host(kind):
    m, x = _fitted(kind)
    flat = m.flat()
    db = flat.device_bank()
    assert tg.traversal_form(db.depth) == "dense"
    q = _rows(3, 257) * np.float32(2.0) + np.float32(3.0)
    q = np.concatenate([q, x.astype(np.float32)])
    dev = m.predict_on_device(q)                       # `_fused`, dense
    kind_, scale, bias = m._device_reduction()
    assert kind_ == kind
    raw = m._device_thresholds
    ref = jax.jit(partial(_loop_fused, depth=db.depth, kind=kind))(
        db.feature, raw, db.left, db.right, db.value, db.roots,
        jnp.float32(scale), jnp.float32(bias), jnp.asarray(q))
    np.testing.assert_allclose(dev, np.asarray(ref, np.float64),
                               rtol=1e-6, atol=0)
    np.testing.assert_allclose(dev, m.predict(q.astype(np.float64)),
                               rtol=1e-4, atol=1e-6)


def _deep_forest():
    rng = np.random.default_rng(5)
    x = rng.standard_normal((600, FEATURES))
    y = np.sin(3 * x[:, 0]) + x[:, 1] * x[:, 2] + rng.standard_normal(600)
    m = RandomForestPredictor(n_trees=3, max_depth=14).fit(x, y)
    assert m.flat().max_depth > tg.DENSE_MAX_DEPTH
    return m, x


@pytest.mark.parametrize("bank", ["shallow", "deep"])
def test_form_counter_and_dispatch_attr(bank):
    if bank == "shallow":
        m, x = _fitted("sum")
        form = "dense"
    else:
        m, x = _deep_forest()
        form = "loop"
    flat = m.flat()
    assert tg.traversal_form(flat.device_bank().depth) == form
    q = x[:37].astype(np.float32)       # a row count no other test uses
    before = tg.residency_counters()
    tracer = Tracer()
    got = m.predict_on_device(q, tracer=tracer)
    after = tg.residency_counters()
    assert after["programs_traced"] - before["programs_traced"] == 1
    assert (after["dense_programs_traced"]
            - before["dense_programs_traced"]) == (form == "dense")
    (dispatch,) = [s for s in tracer.export() if s["name"] == "tree.dispatch"]
    assert dispatch["attrs"] == {"form": form}
    np.testing.assert_allclose(got, m.predict(q.astype(np.float64)),
                               rtol=1e-4, atol=1e-6)


_SHARDED = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    import json, sys
    from functools import partial
    import jax, jax.numpy as jnp, numpy as np
    sys.path.insert(0, {tests!r})
    from test_dense_traversal import _fitted, _loop_fused, _rows
    from repro.kernels import tree_gather as tg

    def bits(a):
        return np.asarray(a, np.float32).view(np.uint32)

    out = {{"devices": len(jax.devices())}}
    for kind in ("sum", "mean"):
        m, x = _fitted(kind)
        flat = m.flat()
        db = flat.device_bank()
        assert db.mesh is not None and db.depth <= tg.DENSE_MAX_DEPTH
        q = np.concatenate([_rows(1, 2050), x.astype(np.float32)])
        xd = db.stage_input(q)                     # row-sharded
        assert tg._row_sharded(xd)
        n = len(q)
        if kind == "sum":
            dense = db.gather_leaves(xd)[:n]
            loop = db._sharded_fn(("test_loop", db.depth),
                                  partial(tg._traverse_loop, depth=db.depth),
                                  out_rank2=True)(*db.bank_args, xd)[:n]
            host = flat.predict_trees(q.astype(np.float64), backend="numpy")
            out["traverse"] = [int((bits(dense) != bits(loop)).sum()),
                               int((bits(dense) != bits(host)).sum())]
        raw = tg.raw_thresholds(flat, m.scaler)
        kind_, scale, bias = m._device_reduction()
        args = (db.feature, raw, db.left, db.right, db.value, db.roots,
                jnp.float32(scale), jnp.float32(bias), xd)
        dense = np.asarray(db.fused(*args[1:2], *args[6:], kind)[:n])
        loop = np.asarray(db._sharded_fn(
            ("test_loop_fused", db.depth, kind),
            partial(_loop_fused, depth=db.depth, kind=kind),
            out_rank2=False)(*args)[:n])
        out["fused_" + kind] = float(np.max(np.abs(dense - loop)
                                            / np.maximum(np.abs(loop), 1e-30)))
    print(json.dumps(out))
""")


@pytest.fixture(scope="module")
def sharded():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env["PYTHONPATH"] = REPO_SRC + os.pathsep + env.get("PYTHONPATH", "")
    env.pop("XLA_FLAGS", None)
    code = _SHARDED.format(tests=os.path.dirname(os.path.abspath(__file__)))
    proc = subprocess.run([sys.executable, "-c", code], capture_output=True,
                          text=True, timeout=300, env=env)
    assert proc.returncode == 0, proc.stderr[-3000:]
    return json.loads(proc.stdout.strip().splitlines()[-1])


@pytest.mark.parametrize("program", ["traverse", "fused_sum", "fused_mean"])
def test_sharded_dense_matches_loop(sharded, program):
    assert sharded["devices"] == 4
    if program == "traverse":
        assert sharded["traverse"] == [0, 0]     # against loop, against host
    else:
        assert sharded[program] <= 1e-6
