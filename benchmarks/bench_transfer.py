"""Transfer benchmark: budgeted adaptation vs the fully-profiled oracle.

Sweeps the measurement budget K for `TransferEngine.adapt` on a
synthetic source→target device pair and reports e2e MAPE (held-out
archs) against the oracle bank trained on a full target profile — the
paper's §6 "small amounts of profiling data" claim as a curve, plus
the measurement counts that claim is about.

Self-contained.  The source suite defaults to the deterministic
cost-model session so the reported curve is reproducible run-to-run
(wall-clock profiling on this container is noisy enough to swamp the
budget effect — the verify gotcha about comparing counts, not
latencies, applies to MAPEs built on re-measured stores too);
``--real`` profiles the source for real instead (warm ProfileStore
across runs), and ``--smoke`` (CI) trims the suite to seconds.

  PYTHONPATH=src python -m benchmarks.bench_transfer [--smoke] [--real]
"""
from __future__ import annotations

import argparse
import os
import time

from repro.core.composition import mape
from repro.core.dataset import synthetic_graphs
from repro.core.profiler import DeviceSetting, ProfileSession
from repro.pipeline import LatencyService, PredictorHub, ProfileStore
from repro.transfer import (CostModelProfileSession, ReplayProfileSession,
                            SyntheticDevice, TransferEngine)
from repro.utils.compile_cache import enable_compile_cache
from benchmarks.common import REPORT_DIR, emit_csv

SOURCE = DeviceSetting("cpu_f32", "float32", "op_by_op")
TARGET = DeviceSetting("sim", "float32", "op_by_op", device="sim")


def run(smoke: bool = False, real: bool = False) -> None:
    n_archs, n_test = (6, 2) if smoke else (14, 4)
    budgets = (4, 8) if smoke else (8, 16, 32, 64)
    graphs = synthetic_graphs(n_archs, resolution=16)
    train, test = graphs[:-n_test], graphs[-n_test:]

    t0 = time.perf_counter()
    if real:
        store = ProfileStore(os.path.join(REPORT_DIR, "datasets",
                                          "transfer_store.jsonl"))
        session = ProfileSession(repeats=1, inner=2, store=store)
    else:
        store = ProfileStore()
        session = CostModelProfileSession(store=store, seed=1)
    for g in graphs:
        session.profile_graph(g, SOURCE)
    t_profile = time.perf_counter() - t0
    n_source = session.measured_ops

    hub = PredictorHub()
    hub.train(store, SOURCE, "gbdt", hparams={"n_stages": 50}, min_samples=3,
              fingerprints=[g.fingerprint() for g in train])

    device = SyntheticDevice("sim", seed=7, noise=0.1, curvature=0.15)
    oracle_sess = ReplayProfileSession(store, device, SOURCE,
                                       store=ProfileStore())
    truth = {g.name: oracle_sess.profile_graph(g, TARGET).e2e_s
             for g in graphs}
    oracle_hub = PredictorHub()
    oracle_hub.train(oracle_sess.store, TARGET, "gbdt",
                     hparams={"n_stages": 50}, min_samples=3,
                     fingerprints=[g.fingerprint() for g in train])
    oracle_svc = LatencyService(oracle_hub, predictor="gbdt")
    y_true = [truth[g.name] for g in test]
    oracle_mape = mape(y_true, [oracle_svc.predict_e2e(g, TARGET).e2e_s
                                for g in test])

    rows = [{
        "name": "oracle",
        "measurements": oracle_sess.measured_ops + oracle_sess.measured_graphs,
        "e2e_mape_pct": f"{100 * oracle_mape:.2f}",
        "derived": f"full target profile; source profile {t_profile:.1f}s "
                   f"({n_source} ops)",
    }]
    for k in budgets:
        target_sess = ReplayProfileSession(store, device, SOURCE)
        t0 = time.perf_counter()
        result = TransferEngine(SOURCE, TARGET, family="gbdt", seed=0).adapt(
            store, hub, target_sess, k)
        t_adapt = time.perf_counter() - t0
        svc = LatencyService(hub, predictor="gbdt")
        m = mape(y_true, [svc.predict_e2e(g, TARGET).e2e_s for g in test])
        assert result.n_measurements <= k, "budget violated"
        rows.append({
            "name": f"budget_k{k}",
            "measurements": result.n_measurements,
            "e2e_mape_pct": f"{100 * m:.2f}",
            "derived": f"{m / max(oracle_mape, 1e-12):.2f}x oracle, "
                       f"adapt {1e3 * t_adapt:.0f} ms, "
                       f"{result.composition}",
        })
    emit_csv("transfer", rows,
             fieldnames=["name", "measurements", "e2e_mape_pct", "derived"])
    if smoke:
        # CI gate: the calibrated path must beat having no calibration
        # at all by construction — assert it served and stayed in budget.
        assert all(float(r["e2e_mape_pct"]) < 100.0 for r in rows), rows


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--smoke", action="store_true",
                    help="tiny suite + tiny budgets (CI)")
    ap.add_argument("--real", action="store_true",
                    help="wall-clock source profiling instead of the "
                         "deterministic cost model")
    args = ap.parse_args()
    run(smoke=args.smoke, real=args.real)


if __name__ == "__main__":
    enable_compile_cache()
    main()
