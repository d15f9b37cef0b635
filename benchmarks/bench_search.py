"""Search benchmark: predictor-in-the-loop NAS vs measure-everything.

Runs a seeded `repro.search` evolution on the deterministic cost-model
session and reports (a) search throughput (generations/sec, candidates
scored), (b) the measurement economics the paper's §1 argument is
about: the search only measures its final front for verification, while
the measure-everything oracle profiles every candidate it evaluates —
the ratio is the "predictor calls avoided" claim as a number, checked
at matched front quality (the oracle front is computed from measured
latencies of the SAME candidate pool, so quality gaps are attributable
to prediction error, not search luck).

Also asserts the engine's determinism contract at full scale: two
invocations and a checkpoint/resume replay must reproduce the identical
front, and each generation costs exactly one predict_batch per device.

A second phase reruns the determinism contract on a random-wired
population (`SearchConfig(family="random_wired")`): arbitrary-fanout
DAGs through the same engine, same one-predict_batch-per-generation
economics, same bit-identical rerun + resume.

Self-contained and deterministic (no wall-clock measurement anywhere);
``--smoke`` (CI) trims the run to seconds.

  PYTHONPATH=src python -m benchmarks.bench_search [--smoke]
"""
from __future__ import annotations

import argparse
import os
import tempfile
import time

import numpy as np

from repro.core.dataset import synthetic_graphs
from repro.core.nas_space import (RandomWiredConfig, decode_genotype,
                                  sample_random_wired)
from repro.core.profiler import DeviceSetting
from repro.pipeline import LatencyService, PredictorHub, ProfileStore
from repro.search import DeviceBudget, SearchConfig, SearchEngine
from repro.search.encoding import decode
from repro.transfer import CostModelProfileSession
from repro.utils.compile_cache import enable_compile_cache
from benchmarks.common import emit_csv

SETTING = DeviceSetting("cpu_f32", "float32", "op_by_op")


def run(smoke: bool = False) -> None:
    n_train = 8 if smoke else 14
    cfg = SearchConfig(
        population_size=16 if smoke else 48,
        generations=5 if smoke else 16,
        children_per_gen=12 if smoke else 40,
        seed=11, resolution=16, front_capacity=6 if smoke else 10,
    )

    store = ProfileStore()
    session = CostModelProfileSession(store=store, seed=3)
    train = synthetic_graphs(n_train, resolution=16)
    for g in train:
        session.profile_graph(g, SETTING)
    hub = PredictorHub()
    hub.train(store, SETTING, "gbdt", hparams={"n_stages": 50}, min_samples=3)
    svc = LatencyService(hub, default_setting=SETTING, predictor="gbdt")
    e2e = [store.get_arch(SETTING, g.fingerprint()).e2e_s for g in train]
    budgets = [DeviceBudget(SETTING, float(np.median(e2e)))]

    # -- the search (never measures a candidate) ----------------------------
    t0 = time.perf_counter()
    engine = SearchEngine(svc, budgets, cfg)
    report = engine.run()
    dt = time.perf_counter() - t0
    assert all(s.predict_calls in (0, len(budgets)) for s in report.stats), \
        "more than one predict_batch per device per generation"
    backend_runs = svc.stats()["backend_runs"]
    assert sum(backend_runs.values()) > 0, "no backend recorded"
    assert backend_runs.get("numpy", 0) > 0, backend_runs  # sub-2^16 slots

    # Determinism contract at benchmark scale: fresh invocation + a
    # checkpoint/resume replay both reproduce the identical front.
    rerun = SearchEngine(svc, budgets, cfg).run()
    assert rerun.front_json() == report.front_json(), "run-to-run mismatch"
    ck = os.path.join(tempfile.mkdtemp(), "search_ck.json")
    half = SearchEngine(svc, budgets, cfg)
    for _ in range(cfg.generations // 2):
        half.step()
    half.save(ck)
    resumed = SearchEngine.load(ck, svc).run()
    assert resumed.front_json() == report.front_json(), "resume mismatch"

    # -- verification: measure ONLY the front --------------------------------
    verify_sess = CostModelProfileSession(seed=3)
    ver = report.verify(verify_sess)
    search_measurements = verify_sess.measured_graphs

    # -- measure-everything oracle over the SAME candidate pool --------------
    oracle_sess = CostModelProfileSession(seed=3)
    space = cfg.space()
    measured: dict = {}
    for digest, gt in engine.genotypes.items():
        g = decode(gt, space)
        measured[digest] = oracle_sess.profile_graph(g, SETTING).e2e_s
    oracle_measurements = oracle_sess.measured_graphs
    ratio = oracle_measurements / max(1, search_measurements)

    # Matched front quality: best measured-feasible quality the oracle
    # finds in the pool vs the best quality on the (predictor-chosen,
    # then measured) front — both under the measured budget.
    budget_s = budgets[0].budget_s
    oracle_best = max(
        (engine.memo[d]["quality"] for d, lat in measured.items()
         if lat <= budget_s), default=float("nan"))
    front_best = max(
        (m.quality for m, row in zip(report.front, ver["rows"])
         if row["measured_s"] <= budget_s), default=float("nan"))
    quality_gap_pct = 100.0 * (oracle_best - front_best) / abs(oracle_best)

    rows = [
        {
            "name": "search",
            "value": f"{report.generations / dt:.2f}",
            "derived": f"generations/sec ({report.generations} gens, "
                       f"{report.candidates_scored} candidates, "
                       f"{report.predict_batch_calls} predict_batch calls, "
                       f"{dt:.1f}s, backends {svc.stats()['backend_runs']})",
        },
        {
            "name": "measurements_search",
            "value": search_measurements,
            "derived": f"front verification only; front MAPE "
                       f"{100 * ver['mape']:.1f}%",
        },
        {
            "name": "measurements_oracle",
            "value": oracle_measurements,
            "derived": "measure-everything over the same candidate pool",
        },
        {
            "name": "measurement_ratio",
            "value": f"{ratio:.1f}",
            "derived": f"oracle/search measurements; quality gap "
                       f"{quality_gap_pct:.2f}% at matched (measured) budget",
        },
    ]
    emit_csv("search", rows, fieldnames=["name", "value", "derived"])

    # Gates: the economics claim (≥50× fewer measurements at full scale)
    # and a sane front at matched quality.
    floor = 5.0 if smoke else 50.0
    assert ratio >= floor, f"measurement ratio {ratio:.1f} < {floor}"
    assert np.isfinite(front_best), "no measured-feasible front member"
    assert quality_gap_pct <= 10.0, \
        f"front quality {quality_gap_pct:.2f}% behind the oracle"
    if not smoke:
        assert report.candidates_scored >= 500, report.candidates_scored


def run_random_wired(smoke: bool = False) -> None:
    """Determinism contract on an arbitrary-fanout population."""
    rwc = RandomWiredConfig(model="mixed", stages=2, nodes_per_stage=6,
                            stem_c=8, channel_scale=0.25, encdec_prob=0.25)
    cfg = SearchConfig(
        population_size=10 if smoke else 24,
        generations=4 if smoke else 10,
        children_per_gen=8 if smoke else 20,
        seed=19, resolution=16, front_capacity=6,
        family="random_wired", rw=rwc.to_json(),
    )
    store = ProfileStore()
    session = CostModelProfileSession(store=store, seed=3)
    train = synthetic_graphs(8, resolution=16)
    train += [decode_genotype(sample_random_wired(s, rwc), cfg.space())
              for s in range(4 if smoke else 8)]
    for g in train:
        session.profile_graph(g, SETTING)
    hub = PredictorHub()
    hub.train(store, SETTING, "gbdt", hparams={"n_stages": 50}, min_samples=3)
    svc = LatencyService(hub, default_setting=SETTING, predictor="gbdt")
    e2e = [store.get_arch(SETTING, g.fingerprint()).e2e_s for g in train]
    budgets = [DeviceBudget(SETTING, float(np.median(e2e)) * 4)]

    t0 = time.perf_counter()
    report = SearchEngine(svc, budgets, cfg).run()
    dt = time.perf_counter() - t0
    assert report.front, "random-wired search produced an empty front"
    assert all(s.predict_calls in (0, len(budgets)) for s in report.stats)
    rerun = SearchEngine(svc, budgets, cfg).run()
    assert rerun.front_json() == report.front_json(), \
        "random-wired run-to-run mismatch"
    ck = os.path.join(tempfile.mkdtemp(), "rw_ck.json")
    half = SearchEngine(svc, budgets, cfg)
    for _ in range(cfg.generations // 2):
        half.step()
    half.save(ck)
    resumed = SearchEngine.load(ck, svc).run()
    assert resumed.front_json() == report.front_json(), \
        "random-wired resume mismatch"
    emit_csv("search_random_wired", [{
        "name": "search_random_wired",
        "value": f"{report.generations / dt:.2f}",
        "derived": f"generations/sec ({report.candidates_scored} candidates, "
                   f"front {len(report.front)}, rerun+resume bit-identical)",
    }], fieldnames=["name", "value", "derived"])


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--smoke", action="store_true",
                    help="tiny population/generations (CI)")
    args = ap.parse_args()
    run(smoke=args.smoke)
    run_random_wired(smoke=args.smoke)


if __name__ == "__main__":
    enable_compile_cache()
    main()
