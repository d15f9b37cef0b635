"""Pipeline benchmark: ProfileStore warm-vs-cold, LatencyService cache.

Quantifies what the unified pipeline buys:
  * cold profiling (every op measured) vs warm re-profiling from a
    persisted ProfileStore (zero measurements),
  * uncached predict_e2e vs fingerprint-LRU-cached repeat queries,
  * batched multi-graph prediction vs one-by-one.

Self-contained (profiles its own small suite); no prebuilt datasets.
"""
from __future__ import annotations

import os
import time

from repro.core.dataset import synthetic_graphs
from repro.core.profiler import DeviceSetting, ProfileSession
from repro.pipeline import LatencyService, ProfileStore
from repro.utils.compile_cache import enable_compile_cache
from benchmarks.common import REPORT_DIR, emit_csv

N_ARCHS = 8
RESOLUTION = 16


def run() -> None:
    setting = DeviceSetting("cpu_f32", "float32", "op_by_op")
    store_path = os.path.join(REPORT_DIR, "datasets", "pipeline_store.jsonl")
    if os.path.exists(store_path):
        os.remove(store_path)
    graphs = synthetic_graphs(N_ARCHS, resolution=RESOLUTION)

    t0 = time.perf_counter()
    svc = LatencyService.build(
        graphs, setting, store=store_path,
        session=ProfileSession(repeats=1, inner=2),
        predictor="gbdt", hparams={"n_stages": 50})
    t_cold = time.perf_counter() - t0
    n_measured = svc.session.measured_ops

    # Warm pass: fresh process-equivalent (new session, store re-read).
    warm = ProfileSession(store=ProfileStore(store_path))
    t0 = time.perf_counter()
    for g in graphs:
        warm.profile_graph(g, setting)
    t_warm = time.perf_counter() - t0
    assert warm.measured_ops == 0, "warm store still measured ops"

    # Prediction latency: uncached vs LRU-cached vs batched.
    probe = synthetic_graphs(16, resolution=RESOLUTION, seed0=500)
    t0 = time.perf_counter()
    for g in probe:
        svc.predict_e2e(g)
    t_uncached = (time.perf_counter() - t0) / len(probe)
    t0 = time.perf_counter()
    for g in probe:
        svc.predict_e2e(g)
    t_cached = (time.perf_counter() - t0) / len(probe)
    svc.clear_cache()
    t0 = time.perf_counter()
    svc.predict_batch(probe)
    t_batched = (time.perf_counter() - t0) / len(probe)

    emit_csv("pipeline", [
        {"name": "profile_cold_s", "value": f"{t_cold:.2f}",
         "derived": f"{n_measured} ops measured"},
        {"name": "profile_warm_s", "value": f"{t_warm:.4f}",
         "derived": f"{t_cold / max(t_warm, 1e-9):.0f}x faster, 0 ops measured"},
        {"name": "predict_uncached_us", "value": f"{1e6 * t_uncached:.0f}",
         "derived": "per graph"},
        {"name": "predict_cached_us", "value": f"{1e6 * t_cached:.0f}",
         "derived": f"{t_uncached / max(t_cached, 1e-9):.0f}x faster"},
        {"name": "predict_batched_us", "value": f"{1e6 * t_batched:.0f}",
         "derived": "per graph, one call per op type"},
    ], fieldnames=["name", "value", "derived"])


if __name__ == "__main__":
    enable_compile_cache()
    run()
