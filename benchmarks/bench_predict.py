"""Prediction fast path: flattened ensembles, backend crossover, residency.

Sections:
  * tree inference — RF/GBDT batch prediction (512 rows × 100 trees),
    per-row node-walk oracle vs flattened struct-of-arrays traversal
    (numpy) vs the jit'd jax gather backend;
  * backend crossover — numpy vs jax (resident bank) vs jax (cold bank,
    re-uploaded per call — the pre-residency behaviour) across a
    rows×trees sweep, plus what "auto" resolves to at each point.
    Written to reports/bench_predict.json with the platform it ran on;
  * fused device scoring — host predict (float64 bounce) vs
    `predict_on_device` (standardize→traverse→reduce→clamp on device);
  * predict_batch — LatencyService multi-graph scoring, cold
    featurization vs warm `GraphFeatures` cache (prediction LRU cleared
    both times, so the delta is featurization only).

Self-contained (fits on synthetic tabular data / profiles a tiny
suite); no prebuilt datasets.  ``--smoke`` shrinks the sweep for CI.
"""
from __future__ import annotations

import argparse
import time

import numpy as np

from repro.core.features import clear_graph_feature_cache
from repro.core.predictors import GBDTPredictor, RandomForestPredictor
from repro.core.predictors.flat import AUTO_JAX_MIN_SLOTS, resolve_backend
from repro.core.dataset import synthetic_graphs
from repro.core.profiler import DeviceSetting, ProfileSession
from repro.pipeline import LatencyService
from repro.utils.compile_cache import enable_compile_cache
from benchmarks.common import emit_bench_json, emit_csv

N_ROWS = 512
N_FEATURES = 16
N_TREES = 100


def _bench(fn, *args, repeats=5):
    fn(*args)                                    # warm (jit/flatten)
    best = float("inf")
    for _ in range(repeats):
        t0 = time.perf_counter()
        fn(*args)
        best = min(best, time.perf_counter() - t0)
    return best


def _crossover(rows_list, smoke):
    """numpy / jax-resident / jax-cold sweep over flush sizes."""
    import jax

    n_trees = 50 if smoke else N_TREES
    rng = np.random.default_rng(7)
    x = np.abs(rng.standard_normal((400, N_FEATURES))) \
        * np.linspace(1, 40, N_FEATURES)
    y = x @ rng.random(N_FEATURES) + 0.2
    m = GBDTPredictor(n_stages=n_trees).fit(x, y)
    flat = m.flat()
    q = np.abs(rng.standard_normal((max(rows_list), N_FEATURES))) \
        * np.linspace(1, 40, N_FEATURES)
    xs = m.scaler.transform(q)

    curve = []
    for rows in rows_list:
        xq = xs[:rows]
        slots = rows * n_trees
        point = {"rows": rows, "trees": n_trees, "slots": slots,
                 "auto_resolves_to": resolve_backend("auto", slots)}
        point["numpy_ms"] = 1e3 * _bench(flat.predict_trees, xq, "numpy")
        point["jax_resident_ms"] = 1e3 * _bench(flat.predict_trees, xq, "jax")

        def jax_cold():
            flat._device_bank = None          # force bank re-upload
            flat.predict_trees(xq, "jax")

        point["jax_cold_bank_ms"] = 1e3 * _bench(jax_cold)
        flat._device_bank = None              # leave a fresh bank behind
        flat.predict_trees(xq, "jax")
        point["auto_ms"] = 1e3 * _bench(flat.predict_trees, xq, "auto")
        curve.append(point)

    # Fused device scoring vs the host path at the largest flush.
    qbig = q
    t_host = _bench(m.predict, qbig)
    q32 = np.asarray(qbig, np.float32)
    t_fused = _bench(m.predict_on_device, q32)
    fused = {"rows": len(qbig), "trees": n_trees,
             "host_float64_ms": 1e3 * t_host,
             "device_fused_ms": 1e3 * t_fused,
             "speedup": t_host / max(t_fused, 1e-12)}

    # Soft acceptance checks (generous slack: shared CI machines).
    big, small = curve[-1], curve[0]
    checks = {
        # Device-resident path must not lose to re-uploading the bank
        # every call at large flushes.
        "resident_not_worse_than_cold": bool(
            big["jax_resident_ms"] <= big["jax_cold_bank_ms"] * 1.15),
        # "auto" keeps small batches on numpy with no regression beyond
        # the resolve_backend call itself.
        "auto_small_batch_is_numpy": small["auto_resolves_to"] == "numpy",
        "auto_small_batch_no_regression": bool(
            small["auto_ms"] <= small["numpy_ms"] * 2.0 + 0.5),
    }
    for name, ok in checks.items():
        assert ok, (name, curve)

    db = flat._device_bank
    return {
        "platform": jax.devices()[0].platform,
        "device_kind": jax.devices()[0].device_kind,
        "device_count": len(jax.devices()),
        "auto_jax_min_slots": AUTO_JAX_MIN_SLOTS,
        "crossover": curve,
        "fused": fused,
        "residency": db.stats() if db is not None else None,
        "checks": checks,
    }


def run(smoke: bool = False) -> None:
    rng = np.random.default_rng(0)
    x = np.abs(rng.standard_normal((400, N_FEATURES))) * np.linspace(1, 40, N_FEATURES)
    y = x @ rng.random(N_FEATURES) + 0.2
    q = np.abs(rng.standard_normal((N_ROWS, N_FEATURES))) * np.linspace(1, 40, N_FEATURES)

    rows = []
    models = [
        ("rf", RandomForestPredictor(n_trees=N_TREES, max_depth=10).fit(x, y)),
        ("gbdt", GBDTPredictor(n_stages=N_TREES).fit(x, y)),
    ]
    for name, m in models:
        t_oracle = _bench(m.predict_oracle, q)
        t_flat = _bench(m.predict, q)
        assert np.array_equal(m.predict(q), m.predict_oracle(q)), \
            f"{name}: flattened path diverged from oracle"
        rows.append({"name": f"{name}_oracle_ms", "value": f"{1e3 * t_oracle:.2f}",
                     "derived": f"{N_ROWS} rows x {N_TREES} trees, per-row node walk"})
        rows.append({"name": f"{name}_flat_ms", "value": f"{1e3 * t_flat:.2f}",
                     "derived": f"{t_oracle / t_flat:.1f}x faster, bit-identical"})
        m.inference_backend = "jax"
        try:
            t_jax = _bench(m.predict, q)
        finally:
            m.inference_backend = "numpy"
        rows.append({"name": f"{name}_jax_ms", "value": f"{1e3 * t_jax:.2f}",
                     "derived": f"{t_oracle / t_jax:.1f}x vs oracle (jit gathers)"})

    # -- backend crossover curve (numpy / jax) -------------------------------
    rows_list = [64, 512, 2048] if smoke else [64, 256, 1024, 4096, 16384]
    xover = _crossover(rows_list, smoke)
    for p in xover["crossover"]:
        derived = [f"auto→{p['auto_resolves_to']}"]
        for k in ("numpy_ms", "jax_resident_ms", "jax_cold_bank_ms",
                  "auto_ms"):
            derived.append(f"{k.removesuffix('_ms')}={p[k]:.2f}ms")
        rows.append({"name": f"crossover_{p['rows']}x{p['trees']}",
                     "value": str(p["slots"]),
                     "derived": " ".join(derived)})
    f = xover["fused"]
    rows.append({"name": "fused_device_ms",
                 "value": f"{f['device_fused_ms']:.2f}",
                 "derived": f"{f['speedup']:.1f}x vs host float64 "
                            f"({f['host_float64_ms']:.2f}ms) at "
                            f"{f['rows']} rows"})
    emit_bench_json("bench_predict", xover)

    # -- predict_batch featurization: cold vs warm GraphFeatures cache ------
    setting = DeviceSetting("cpu_f32", "float32", "op_by_op")
    graphs = synthetic_graphs(6, resolution=16)
    svc = LatencyService.build(
        graphs, setting,
        session=ProfileSession(warmup=0, inner=1, repeats=1,
                               e2e_inner=1, e2e_repeats=1),
        predictor="gbdt", hparams={"n_stages": 50})
    probe = synthetic_graphs(16, resolution=16, seed0=900)

    clear_graph_feature_cache()
    svc.clear_cache()
    t0 = time.perf_counter()
    svc.predict_batch(probe)
    t_cold = time.perf_counter() - t0

    svc.clear_cache()                  # drop report LRU, keep feature cache
    t0 = time.perf_counter()
    svc.predict_batch(probe)
    t_warm = time.perf_counter() - t0

    rows.append({"name": "predict_batch_cold_us", "value": f"{1e6 * t_cold / len(probe):.0f}",
                 "derived": "per graph, featurizers run"})
    rows.append({"name": "predict_batch_warm_us", "value": f"{1e6 * t_warm / len(probe):.0f}",
                 "derived": f"{t_cold / max(t_warm, 1e-9):.1f}x faster, GraphFeatures cache"})

    emit_csv("predict", rows, fieldnames=["name", "value", "derived"])


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="small sweep for CI")
    args = ap.parse_args()
    enable_compile_cache()
    run(smoke=args.smoke)
