"""Compiled prediction fast path (no optional deps — run everywhere).

Covers: FlatEnsemble structure + serialization rebuild, the jax gather
backend, `feature_names` lazy probe, `GraphFeatures` + its fingerprint
LRU, the bounded `ProfileSession.fn_cache`, and featurize-once
profiling.  Property-based flattened-vs-oracle parity lives in
tests/test_predictors.py behind the hypothesis guard.
"""
import json

import numpy as np
import pytest

from repro.core import features as features_mod
from repro.core.features import (
    GraphFeatures, clear_graph_feature_cache, feature_names, featurize,
    graph_feature_cache_info, graph_features,
)
from repro.core.ir import OpGraph
from repro.core.predictors import (
    FlatEnsemble, GBDTPredictor, RandomForestPredictor, load_predictor,
)
from repro.core.predictors.trees import RegressionTree
from repro.core.profiler import DeviceSetting, ProfileSession
from repro.pipeline import ProfileStore
from repro.utils.lru import LRUCache

SETTING = DeviceSetting("cpu_f32", "float32", "op_by_op")


def _data(n=200, d=8, seed=0):
    rng = np.random.default_rng(seed)
    x = np.abs(rng.standard_normal((n, d))) * np.linspace(1, 30, d)
    y = x @ rng.random(d) + 0.1
    return x, y


def tiny_graph(name="t", ch=4):
    g = OpGraph(name)
    x0 = g.add_input((1, 4, 4, ch))
    (c1,) = g.add_op("conv2d", [x0], [(1, 4, 4, ch)],
                     {"kernel_h": 3, "kernel_w": 3, "stride": 1, "groups": 1})
    (e1,) = g.add_op("elementwise", [c1], [(1, 4, 4, ch)], {"ew_kind": "add"})
    (m1,) = g.add_op("mean", [e1], [(1, ch)])
    g.mark_output(m1)
    g.validate()
    return g


# ---------------------------------------------------------------------------
# FlatEnsemble structure + serialization
# ---------------------------------------------------------------------------

class TestFlatEnsemble:
    def test_structure_invariants(self):
        x, y = _data()
        m = GBDTPredictor(n_stages=10).fit(x, y)
        flat = m.flat()
        assert flat.n_trees == 10
        assert flat.n_nodes == sum(len(t.nodes) for t in m.trees)
        leaves = flat.feature < 0
        # Leaves self-loop; internal children stay in-bank and differ.
        idx = np.arange(flat.n_nodes)
        assert np.array_equal(flat.left[leaves], idx[leaves])
        assert np.array_equal(flat.right[leaves], idx[leaves])
        internal = ~leaves
        assert (flat.left[internal] != flat.right[internal]).all()
        assert flat.left.min() >= 0 and flat.right.max() < flat.n_nodes
        assert flat.max_depth >= 1

    def test_unfitted_raises(self):
        with pytest.raises(ValueError):
            FlatEnsemble.from_trees([])
        with pytest.raises(ValueError):
            FlatEnsemble.from_trees([RegressionTree()])

    @pytest.mark.parametrize("family,kw", [
        (RandomForestPredictor, {"n_trees": 6}),
        (GBDTPredictor, {"n_stages": 30}),
    ])
    def test_roundtrip_rebuilds_flat_arrays_bit_identically(self, family, kw):
        x, y = _data()
        m = family(**kw).fit(x, y)
        m2 = load_predictor(json.loads(json.dumps(m.to_json())))
        f1, f2 = m.flat(), m2.flat()
        for name in ("feature", "threshold", "left", "right", "value", "roots"):
            a, b = getattr(f1, name), getattr(f2, name)
            assert a.dtype == b.dtype and np.array_equal(a, b), name
        assert f1.max_depth == f2.max_depth
        assert np.array_equal(m.predict(x), m2.predict(x))

    def test_bank_load_is_warm(self):
        from repro.core.composition import PredictorBank

        x, y = _data()
        bank = PredictorBank(setting="cpu_f32")
        bank.predictors["conv2d"] = GBDTPredictor(n_stages=10).fit(x, y)
        bank2 = PredictorBank.from_json(json.loads(json.dumps(bank.to_json())))
        # from_json warms: flattened state exists before the first query.
        assert bank2.predictors["conv2d"]._flat is not None

    def test_jax_backend_matches_numpy(self):
        pytest.importorskip("jax")
        x, y = _data()
        m = GBDTPredictor(n_stages=40).fit(x, y)
        q, _ = _data(n=257, seed=1)
        flat = m.flat()
        xs = m.scaler.transform(q)
        ref = flat.predict_trees(xs, backend="numpy")
        got = flat.predict_trees(xs, backend="jax")
        assert got.shape == ref.shape
        # jax runs at its default precision (float32 unless x64): close,
        # not necessarily bit-equal.
        np.testing.assert_allclose(got, ref, rtol=1e-4, atol=1e-7)

    def test_unknown_backend_raises(self):
        x, y = _data()
        t = RegressionTree(max_depth=3).fit(x, y)
        with pytest.raises(ValueError):
            t.flat().predict_trees(x, backend="cuda")


# ---------------------------------------------------------------------------
# feature_names lazy probe (satellite regression)
# ---------------------------------------------------------------------------

class TestFeatureNames:
    def test_names_without_prior_featurize(self):
        # Regression: indexing the name cache raised KeyError for any op
        # type whose featurizer had never run in this process.
        features_mod._NAME_CACHE.pop("ssd_scan", None)
        names = feature_names("ssd_scan")
        assert names == ["batch", "seq", "heads", "head_dim", "state", "flops"]

    def test_names_match_real_featurization(self):
        g = tiny_graph()
        features_mod._NAME_CACHE.pop("conv2d", None)
        probed = feature_names("conv2d")
        real_names, vec = featurize(g, g.nodes[0])
        assert probed == real_names and len(vec) == len(probed)

    def test_unknown_type_raises(self):
        with pytest.raises(KeyError):
            feature_names("not_an_op")

    def test_every_registered_type_probes(self):
        for op_type in features_mod._FEATURIZERS:
            assert len(feature_names(op_type)) > 0


# ---------------------------------------------------------------------------
# GraphFeatures + fingerprint LRU
# ---------------------------------------------------------------------------

class TestGraphFeatures:
    def test_matches_per_node_featurize(self):
        g = tiny_graph()
        gf = GraphFeatures.from_graph(g)
        assert gf.num_nodes == 3
        for k, node in enumerate(g.nodes):
            names, vec = featurize(g, node)
            assert gf.node_names(k) == names
            assert np.array_equal(gf.node_features(k), vec)
        assert sorted(gf.matrix) == ["conv2d", "elementwise", "mean"]
        for t, mat in gf.matrix.items():
            assert mat.shape[0] == len(gf.index[t])

    def test_type_grouping_row_order(self):
        g = OpGraph("two")
        x0 = g.add_input((1, 4, 4, 2))
        (e1,) = g.add_op("elementwise", [x0], [(1, 4, 4, 2)], {"ew_kind": "add"})
        (e2,) = g.add_op("elementwise", [e1], [(1, 4, 4, 2)], {"ew_kind": "mul"})
        g.mark_output(e2)
        gf = GraphFeatures.from_graph(g)
        assert list(gf.index["elementwise"]) == [0, 1]
        assert gf.slots == [("elementwise", 0), ("elementwise", 1)]
        assert np.array_equal(gf.matrix["elementwise"][1],
                              featurize(g, g.nodes[1])[1])

    def test_cache_hit_returns_same_object(self):
        clear_graph_feature_cache()
        g = tiny_graph()
        gf1 = graph_features(g)
        gf2 = graph_features(g)
        assert gf1 is gf2
        assert graph_feature_cache_info()["size"] == 1
        # Structurally identical graph → same fingerprint → same entry.
        assert graph_features(tiny_graph()) is gf1

    def test_cache_bounded(self):
        clear_graph_feature_cache()
        cap = graph_feature_cache_info()["probation_capacity"]
        # Unpinned inserts (one-shot candidates) only cycle probation.
        for i in range(cap + 5):
            graph_features(tiny_graph(ch=i + 1))
        info = graph_feature_cache_info()
        assert info["probation"] == cap
        assert info["protected"] == 0
        assert info["size"] <= info["capacity"]

    def test_pinned_graphs_survive_one_shot_scan(self, monkeypatch):
        # Search-workload thrash regression: scoring thousands of
        # one-shot candidate fingerprints must not evict the pinned
        # (profiled/training) graphs' entries.
        clear_graph_feature_cache()
        train = tiny_graph(ch=3)
        gf = graph_features(train, pin=True)
        cap = graph_feature_cache_info()["probation_capacity"]
        for i in range(cap + 50):                 # a full probation cycle
            graph_features(tiny_graph(ch=i + 10))
        calls = {"n": 0}
        real = features_mod.featurize

        def counting(graph, node):
            calls["n"] += 1
            return real(graph, node)

        monkeypatch.setattr(features_mod, "featurize", counting)
        assert graph_features(train) is gf        # served from protected
        assert calls["n"] == 0
        info = graph_feature_cache_info()
        assert info["protected"] == 1
        assert info["probation"] == info["probation_capacity"]


# ---------------------------------------------------------------------------
# Bounded fn_cache + featurize-once profiling (satellites)
# ---------------------------------------------------------------------------

class TestLRUCache:
    def test_eviction_order(self):
        c = LRUCache(maxsize=2)
        c["a"], c["b"] = 1, 2
        assert c.get("a") == 1          # refresh a → b is now LRU
        c["c"] = 3
        assert "b" not in c and "a" in c and "c" in c

    def test_getitem_refreshes(self):
        c = LRUCache(maxsize=2)
        c["a"], c["b"] = 1, 2
        _ = c["a"]
        c["c"] = 3
        assert list(c) == ["a", "c"]


class TestProfileSessionFastPath:
    def fast_session(self, **kw):
        return ProfileSession(warmup=0, inner=1, repeats=1,
                              e2e_inner=1, e2e_repeats=1, **kw)

    def test_fn_cache_bounded_and_in_stats(self):
        s = self.fast_session(fn_cache_size=2)
        # Capacity grows to cover the largest single graph (eviction
        # mid-profile would re-jit ops the executor just compiled) …
        s.profile_graph(tiny_graph(), SETTING)   # 3 distinct op signatures
        stats = s.stats()
        assert stats["fn_cache_capacity"] == 3
        assert stats["fn_cache_size"] <= 3
        # … but stays bounded across a suite: 5 graphs × 3 distinct
        # signatures compile 15 fns, the cache never exceeds 3.
        for ch in (6, 8, 10, 12):       # differs from the first graph's ch=4
            s.profile_graph(tiny_graph(ch=ch), SETTING)
        stats = s.stats()
        assert stats["fn_cache_capacity"] == 3
        assert stats["fn_cache_size"] <= 3
        assert stats["measured_ops"] == 15
        assert stats["latency_cache_size"] == 15  # latencies stay unbounded

    def test_featurize_once_per_node(self, monkeypatch, tmp_path):
        clear_graph_feature_cache()
        calls = {"n": 0}
        real = features_mod.featurize

        def counting(graph, node):
            calls["n"] += 1
            return real(graph, node)

        monkeypatch.setattr(features_mod, "featurize", counting)
        store = ProfileStore(str(tmp_path / "s.jsonl"))
        s = self.fast_session(store=store)
        s.profile_graph(tiny_graph(), SETTING)
        # One featurization per node (store write reuses it); the old
        # path ran measure_op's + profile_graph's featurize separately.
        assert calls["n"] == 3

    def test_store_features_match_direct(self, tmp_path):
        store = ProfileStore(str(tmp_path / "s.jsonl"))
        s = self.fast_session(store=store)
        g = tiny_graph()
        s.profile_graph(g, SETTING)
        rec = store.arch_records(SETTING)[0]
        for op, node in zip(rec.ops, g.nodes):
            names, vec = featurize(g, node)
            assert op.feature_names == names
            assert op.features == [float(v) for v in vec]


# ---------------------------------------------------------------------------
# Device residency + three-tier backend (PR 6)
# ---------------------------------------------------------------------------

class TestDeviceResidency:
    def test_bank_uploaded_once_across_flushes(self):
        # Satellite regression: predict_trees_jax used to rebuild its
        # device arrays per ensemble lazily but re-upload x every call
        # with nothing pinning the bank's lifecycle; now the bank rides
        # a DeviceBank that survives across flushes.
        pytest.importorskip("jax")
        x, y = _data()
        m = GBDTPredictor(n_stages=10).fit(x, y)
        flat = m.flat()
        xs = m.scaler.transform(x)
        flat.predict_trees(xs, backend="jax")
        db = flat._device_bank
        assert db is not None and db.uploads == 1
        for _ in range(3):
            flat.predict_trees(xs, backend="jax")
        # No per-call host→device transfer of bank arrays: same bank
        # object, upload count pinned at one; only inputs are staged.
        assert flat._device_bank is db
        assert db.uploads == 1
        assert db.inputs_staged == 4

    def test_invalidated_on_refit(self):
        pytest.importorskip("jax")
        x, y = _data()
        m = GBDTPredictor(n_stages=5).fit(x, y)
        m.flat().predict_trees(m.scaler.transform(x), backend="jax")
        old = m.flat()._device_bank
        assert old is not None
        m.fit(x, y + 1.0)                 # retrain → flat (and bank) drop
        assert m._flat is None and m._device_thresholds is None
        m.flat().predict_trees(m.scaler.transform(x), backend="jax")
        assert m.flat()._device_bank is not old

    def test_predict_on_device_matches_host_predict(self):
        pytest.importorskip("jax")
        x, y = _data()
        for m in (GBDTPredictor(n_stages=20).fit(x, y),
                  RandomForestPredictor(n_trees=6).fit(x, y)):
            host = m.predict(x)
            dev = m.predict_on_device(np.asarray(x, np.float32))
            np.testing.assert_allclose(dev, host, rtol=1e-3, atol=1e-5)
            assert (dev >= 0).all()

    @pytest.mark.parametrize("seed", range(3))
    def test_device_routes_exact_ties_like_host(self, seed):
        # Trained on even values, queried on all: odd queries sit exactly
        # on split midpoints, where the float64 host's choice is a matter
        # of rounding.  Column 1 is FLOP-sized and clustered.  Both are
        # float32-representable, so the device must route every row as
        # the host does: only the float32 leaf sum may differ.
        rng = np.random.default_rng(seed)

        def feats(k):
            k = k.astype(np.float64)
            return np.column_stack([k[:, 0], 2.0 ** 31 + 2.0 ** 20 * k[:, 1],
                                    k[:, 2]])

        x = feats(2 * rng.integers(1, 32, (300, 3)))
        y = x[:, 0] * 1e-6 + (x[:, 1] - 2.0 ** 31) * 1e-12 + x[:, 2] * 2e-6
        m = GBDTPredictor(n_stages=30).fit(x, y)
        q = feats(rng.integers(2, 64, (2000, 3)))
        host = m.predict(q)
        dev = m.predict_on_device(q.astype(np.float32))
        np.testing.assert_allclose(dev, host, rtol=1e-5, atol=1e-12)

    def test_device_stats_lazy(self):
        pytest.importorskip("jax")
        x, y = _data()
        m = GBDTPredictor(n_stages=5).fit(x, y)
        assert m.device_stats() is None      # nothing resident yet
        m.flat().predict_trees(m.scaler.transform(x), backend="jax")
        st = m.device_stats()
        assert st is not None and st["uploads"] == 1
        assert st["n_trees"] == 5 and st["nbytes"] > 0


class TestBackendTiers:
    def test_resolve_three_tiers(self):
        from repro.core.predictors import flat as flat_mod

        assert flat_mod.resolve_backend("auto", 100) == "numpy"
        assert flat_mod.resolve_backend(
            "auto", flat_mod.AUTO_JAX_MIN_SLOTS - 1) == "numpy"
        assert flat_mod.resolve_backend(
            "auto", flat_mod.AUTO_JAX_MIN_SLOTS) == "jax"
        assert flat_mod.resolve_backend("auto", 1 << 24) == "jax"
        # Explicit backends pass through untouched.
        for b in ("numpy", "jax"):
            assert flat_mod.resolve_backend(b, 1) == b

    def test_auto_resolving_to_numpy_is_bit_exact(self):
        # "auto" must never silently change reports when it resolves to
        # numpy: small-batch auto == explicit numpy, bit for bit.
        from repro.pipeline import LatencyService

        graphs = [tiny_graph(f"g{i}", ch=2 * i + 2) for i in range(6)]
        auto_svc = LatencyService.build(graphs, SETTING,
                                        predictor="gbdt")
        assert auto_svc.inference_backend == "auto"
        np_svc = LatencyService(auto_svc.hub, default_setting=SETTING,
                                predictor="gbdt", inference_backend="numpy")
        auto_reports = auto_svc.predict_batch(graphs, SETTING)
        np_reports = np_svc.predict_batch(graphs, SETTING)
        for a, b in zip(auto_reports, np_reports):
            assert a.e2e_s == b.e2e_s
            assert a.per_op == b.per_op
        runs = auto_svc.stats()["backend_runs"]
        assert runs.get("numpy", 0) > 0          # the tier actually ran
        assert runs.get("jax", 0) == 0


class TestServiceDevicePath:
    def _service(self):
        from repro.pipeline import LatencyService

        graphs = [tiny_graph(f"g{i}", ch=2 * i + 2) for i in range(6)]
        svc = LatencyService.build(graphs, SETTING, predictor="gbdt")
        return svc, graphs

    def test_fused_device_flush(self, monkeypatch):
        pytest.importorskip("jax")
        from repro.core.predictors import flat as flat_mod

        svc, graphs = self._service()
        np_reports = svc.predict_batch(graphs, SETTING)
        svc.clear_cache()
        # Force the jax tier for any batch size: the flush must route
        # through the fused device path (tallied separately) and stay
        # close to the float64 host reports.
        monkeypatch.setattr(flat_mod, "AUTO_JAX_MIN_SLOTS", 1)
        dev_reports = svc.predict_batch(graphs, SETTING)
        stats = svc.stats()
        assert stats["backend_runs"].get("jax", 0) > 0
        assert stats["device_fused_runs"] > 0
        for a, b in zip(dev_reports, np_reports):
            np.testing.assert_allclose(a.e2e_s, b.e2e_s,
                                       rtol=1e-3, atol=1e-6)
        res = stats["device_residency"]
        assert res["banks"] > 0 and res["bytes"] > 0
        assert res["lifetime"]["banks_built"] >= res["banks"]

    def test_stats_report_residency_without_device_use(self):
        svc, graphs = self._service()
        svc.predict_batch(graphs, SETTING)       # numpy tier only
        res = svc.stats()["device_residency"]
        assert res["banks"] == 0 and res["bytes"] == 0
