"""Predictor family tests + hypothesis property tests on invariants."""
import numpy as np
import pytest

pytest.importorskip("hypothesis")  # optional dep — see requirements-dev.txt
from hypothesis import given, settings, strategies as st

from repro.core.predictors import (
    GBDTPredictor, LassoPredictor, MLPPredictor, RandomForestPredictor,
    Standardizer, make_predictor,
)
from repro.core.predictors.trees import RegressionTree


def _linear_data(n=300, d=6, seed=0):
    rng = np.random.default_rng(seed)
    x = np.abs(rng.standard_normal((n, d))) * np.linspace(1, 50, d)
    w = np.array([2.0, 0, 0.5, 0, 0, 1.0])
    y = x @ w + 0.3
    return x, y


@pytest.mark.parametrize("name,tol", [
    ("lasso", 0.05), ("rf", 0.25), ("gbdt", 0.10), ("mlp", 0.30)])
def test_predictor_fits_linear_relation(name, tol):
    x, y = _linear_data()
    m = make_predictor(name, **({"max_epochs": 1200} if name == "mlp" else {}))
    m.fit(x[:250], y[:250])
    assert m.mape(x[250:], y[250:]) < tol


def test_lasso_nonneg_weights():
    x, y = _linear_data()
    m = LassoPredictor(alpha=1e-3).fit(x, y)
    assert (m.feature_weights >= 0).all()


def test_lasso_sparsity_increases_with_alpha():
    x, y = _linear_data()
    w_small = LassoPredictor(alpha=1e-4).fit(x, y).feature_weights
    w_big = LassoPredictor(alpha=10.0).fit(x, y).feature_weights
    assert (w_big > 1e-8).sum() <= (w_small > 1e-8).sum()


def test_predictions_nonnegative():
    x, y = _linear_data()
    for name in ("lasso", "rf", "gbdt"):
        m = make_predictor(name).fit(x, y)
        assert (m.predict(-np.abs(x)) >= 0).all()


class TestStandardizer:
    @given(st.integers(2, 40), st.integers(1, 6))
    @settings(max_examples=20, deadline=None)
    def test_transform_zero_mean_unit_std(self, n, d):
        rng = np.random.default_rng(n * 7 + d)
        x = rng.standard_normal((n, d)) * 10 + 5
        s = Standardizer().fit(x)
        z = s.transform(x)
        np.testing.assert_allclose(z.mean(axis=0), 0, atol=1e-9)
        keep = x.std(axis=0) > 1e-12
        np.testing.assert_allclose(z.std(axis=0)[keep], 1, atol=1e-9)

    def test_constant_feature_no_nan(self):
        x = np.ones((10, 3))
        z = Standardizer().fit(x).transform(x)
        assert np.isfinite(z).all()


class TestRegressionTree:
    @given(st.integers(5, 60))
    @settings(max_examples=15, deadline=None)
    def test_perfect_split_recovery(self, n):
        rng = np.random.default_rng(n)
        x = rng.standard_normal((n, 2))
        y = np.where(x[:, 0] > 0, 5.0, 1.0)
        t = RegressionTree(max_depth=3).fit(x, y)
        pred = t.predict(x)
        assert np.abs(pred - y).max() < 1e-9

    def test_weighted_fit_prefers_heavy_samples(self):
        x = np.array([[0.0], [1.0]] * 20)
        y = np.array([1.0, 10.0] * 20)
        w = np.array([100.0, 1e-6] * 20)
        t = RegressionTree(max_depth=1, min_samples_split=2).fit(x, y, sample_weight=w)
        # With all weight on y=1 samples, a depth-0-equivalent leaf ≈ 1.
        assert abs(t.predict(np.array([[0.0]]))[0] - 1.0) < 1e-3

    def test_monotone_feature_scaling_invariance(self):
        x, y = _linear_data(100)
        t1 = RegressionTree(max_depth=4, seed=1).fit(x, y)
        t2 = RegressionTree(max_depth=4, seed=1).fit(x * 100.0, y)
        np.testing.assert_allclose(t1.predict(x), t2.predict(x * 100.0), rtol=1e-9)


def test_gbdt_improves_with_stages():
    x, y = _linear_data(400, seed=3)
    y = y + 0.1 * x[:, 0] ** 2
    few = GBDTPredictor(n_stages=5).fit(x[:300], y[:300]).mape(x[300:], y[300:])
    many = GBDTPredictor(n_stages=150).fit(x[:300], y[:300]).mape(x[300:], y[300:])
    assert many < few


# ---------------------------------------------------------------------------
# Flattened fast path ≡ node-walk oracle (property tests)
# ---------------------------------------------------------------------------

def _random_regression(n, d, seed):
    rng = np.random.default_rng(seed)
    x = rng.standard_normal((n, d)) * np.linspace(1, 20, d)
    y = x @ rng.random(d) + rng.standard_normal(n)
    return x, y


class TestFlattenedParity:
    """Batched struct-of-arrays traversal must be bit-identical to the
    per-row node walk — including on training rows, which can sit
    exactly on split thresholds."""

    @given(st.integers(10, 120), st.integers(1, 6), st.integers(1, 10),
           st.integers(0, 10_000))
    @settings(max_examples=25, deadline=None)
    def test_tree_parity(self, n, d, depth, seed):
        x, y = _random_regression(n, d, seed)
        t = RegressionTree(max_depth=depth, seed=seed).fit(x, y)
        q = np.vstack([x, _random_regression(64, d, seed + 1)[0]])
        assert np.array_equal(t.predict(q), t.predict_oracle(q))

    @given(st.integers(30, 100), st.integers(2, 5), st.integers(1, 8),
           st.integers(0, 10_000))
    @settings(max_examples=10, deadline=None)
    def test_rf_parity(self, n, d, n_trees, seed):
        x, y = _random_regression(n, d, seed)
        m = RandomForestPredictor(n_trees=n_trees, max_depth=6, seed=seed).fit(x, y)
        q = np.vstack([x, _random_regression(32, d, seed + 1)[0]])
        assert np.array_equal(m.predict(q), m.predict_oracle(q))

    @given(st.integers(30, 100), st.integers(2, 5), st.integers(1, 40),
           st.integers(0, 10_000))
    @settings(max_examples=10, deadline=None)
    def test_gbdt_parity(self, n, d, n_stages, seed):
        x, y = _random_regression(n, d, seed)
        m = GBDTPredictor(n_stages=n_stages, seed=seed).fit(x, y)
        q = np.vstack([x, _random_regression(32, d, seed + 1)[0]])
        assert np.array_equal(m.predict(q), m.predict_oracle(q))

    def test_single_leaf_tree(self):
        # Constant labels → depth-0 tree (root is the only node).
        x = np.ones((10, 3))
        y = np.full(10, 7.0)
        t = RegressionTree().fit(x, y)
        assert t.flat().max_depth == 0
        assert np.array_equal(t.predict(x), np.full(10, 7.0))


class TestBackendParity:
    """numpy / jax agreement on random banks.

    The device tier runs float32 while numpy runs float64, so two error
    sources exist: leaf-value rounding (bounded by the documented
    rtol=1e-4 tolerance) and ROUTING divergence when a query lands
    within float32 epsilon of a split threshold (a near-tie).  The
    property: every row either agrees within tolerance, or the bank
    provably contains a near-tie for that row — float32 disagreement is
    only ever tie-breaking, never wrong traversal.
    """

    # Fixed shapes keep jit caches warm across examples.
    N_ROWS, N_DIMS, N_STAGES = 24, 4, 5

    def _bank(self, seed):
        pytest.importorskip("jax")
        rng = np.random.default_rng(seed)
        x = np.abs(rng.standard_normal((80, self.N_DIMS))) * 10
        y = x @ rng.random(self.N_DIMS) + 0.1
        m = GBDTPredictor(n_stages=self.N_STAGES, max_depth=3).fit(x, y)
        return m, rng

    @staticmethod
    def _near_tie(flat, xs, row, rel=8 * np.finfo(np.float32).eps):
        internal = flat.feature >= 0
        f = flat.feature[internal]
        thr = flat.threshold[internal]
        gap = np.abs(xs[row, f] - thr)
        return bool((gap <= rel * np.maximum(1.0, np.abs(thr))).any())

    @given(st.integers(0, 10_000))
    @settings(max_examples=15, deadline=None)
    def test_backends_agree_or_near_tie(self, seed):
        m, rng = self._bank(seed)
        flat = m.flat()
        xs = m.scaler.transform(
            np.abs(rng.standard_normal((self.N_ROWS, self.N_DIMS))) * 10)
        # Plant near-ties: aim half the rows directly at split
        # thresholds so tie-handling is exercised, not just sampled.
        internal = flat.feature >= 0
        if internal.any():
            nodes = np.flatnonzero(internal)
            for row in range(0, self.N_ROWS, 2):
                j = nodes[rng.integers(len(nodes))]
                xs[row, flat.feature[j]] = flat.threshold[j]
        ref = flat.predict_trees(xs, backend="numpy")
        jx = flat.predict_trees(xs, backend="jax")
        ok = np.isclose(jx, ref, rtol=2e-4, atol=1e-6).all(axis=1)
        for row in np.flatnonzero(~ok):
            assert self._near_tie(flat, xs, row), (
                f"row {row} disagrees with no near-tie threshold")

    @given(st.integers(0, 10_000))
    @settings(max_examples=10, deadline=None)
    def test_f32_representable_inputs_route_identically(self, seed):
        # When inputs and thresholds are exactly float32-representable,
        # routing cannot diverge: parity becomes (near-)exact equality
        # of the selected leaf values after one f64→f32 rounding.
        m, rng = self._bank(seed + 31337)
        flat = m.flat()
        flat.threshold = flat.threshold.astype(np.float32).astype(np.float64)
        xs = m.scaler.transform(
            np.abs(rng.standard_normal((self.N_ROWS, self.N_DIMS))) * 10)
        xs = xs.astype(np.float32).astype(np.float64)
        ref = flat.predict_trees(xs, backend="numpy")
        jx = flat.predict_trees(xs, backend="jax")
        np.testing.assert_allclose(jx, ref, rtol=1e-6, atol=1e-7)
