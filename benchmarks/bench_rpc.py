"""RPC serving benchmark: micro-batched vs unbatched request throughput.

32 concurrent client threads hammer the serving front-end with distinct
single-graph requests.  Phase "unbatched" forces ``max_batch=1`` — every
request pays its own `predict_batch([g])` (per-op-type dispatch, report
assembly); phase "batched" lets the `MicroBatcher` coalesce (one
predictor call per op type across the whole flush).  Both phases run
the numpy float64 backend so predictions are **bit-identical** between
phases and against direct single-threaded `predict_e2e` — the speedup
is pure call-amortization, not precision drift.  Reported per phase:
requests/sec, p50/p99 request latency, batches and average batch size.

A "degraded mode" phase re-runs the batched workload under a seeded
chaos plan (10% of flushes fail with retryable E_UNAVAILABLE) with
clients retrying until success, and reports throughput/p99 retained
versus the clean run — answers stay bit-identical either way.

A further "auto backend under load" phase scores NAS-scale batches
(``max_batch`` in the hundreds) under ``inference_backend="auto"`` and
reports the `backend_runs` mix — full runs cross the 2¹⁶ row×tree
threshold, so the jax gather kernel engages exactly as PR 4's
auto-threshold intended (numpy-vs-jax agreement reported as max |Δ|,
the jax path runs float32 by design).

Self-contained (deterministic cost-model source); ``--smoke`` (CI)
trims graph counts but keeps concurrency at 32 and still asserts the
≥5× batched-throughput bar and bit-identity.

  PYTHONPATH=src python -m benchmarks.bench_rpc [--smoke]
"""
from __future__ import annotations

import argparse
import threading
import time

import numpy as np

from repro.core.dataset import synthetic_graphs
from repro.core.nas_space import NASSpaceConfig, sample_architecture
from repro.core.profiler import DeviceSetting
from repro.obs import (AlertEngine, AlertRule, MetricsTimeline,
                       Observability)
from repro.pipeline import LatencyService, PredictorHub, ProfileStore
from repro.rpc.batcher import BatchPolicy, MicroBatcher, MonotonicClock
from repro.rpc.chaos import FaultPlan, FaultSpec
from repro.rpc.protocol import E_UNAVAILABLE, RPCError
from repro.transfer import CostModelProfileSession
from repro.utils.compile_cache import enable_compile_cache
from benchmarks.common import emit_bench_json, emit_csv

SETTING = DeviceSetting("cpu_f32", "float32", "op_by_op")
SPACE = NASSpaceConfig(resolution=16)
CONCURRENCY = 32
WINDOW = 4          # in-flight requests per client thread (pipelining)
MAX_BATCH = 64      # the batched phase's coalescing cap


def build_service(n_train: int, n_stages: int, backend: str,
                  obs: Observability = None) -> LatencyService:
    store = ProfileStore()
    session = CostModelProfileSession(store=store, seed=3)
    for g in synthetic_graphs(n_train, resolution=16):
        session.profile_graph(g, SETTING)
    hub = PredictorHub()
    hub.train(store, SETTING, "gbdt", hparams={"n_stages": n_stages},
              min_samples=3)
    return LatencyService(hub, default_setting=SETTING, predictor="gbdt",
                          inference_backend=backend, obs=obs)


def drive(service: LatencyService, graphs, policy: BatchPolicy,
          window: int = WINDOW, obs: Observability = None):
    """CONCURRENCY threads push ``graphs`` through one batcher, each
    keeping up to ``window`` requests in flight (a pipelined client);
    returns (wall_s, per-request latencies, batcher stats, reports)."""
    service.clear_cache()
    batcher = MicroBatcher(service, policy, clock=MonotonicClock(tick_s=1e-3),
                           obs=obs)
    index_chunks = [list(range(len(graphs)))[i::CONCURRENCY]
                    for i in range(CONCURRENCY)]
    lat = [0.0] * len(graphs)
    out = [None] * len(graphs)
    barrier = threading.Barrier(CONCURRENCY + 1)

    def worker(tid):
        barrier.wait()
        mine = index_chunks[tid]
        for j in range(0, len(mine), window):
            futs = []
            for idx in mine[j:j + window]:
                futs.append((idx, time.perf_counter(),
                             batcher.submit(graphs[idx])))
            for idx, t0, fut in futs:
                out[idx] = fut.result(60)
                lat[idx] = time.perf_counter() - t0

    threads = [threading.Thread(target=worker, args=(i,))
               for i in range(CONCURRENCY)]
    for t in threads:
        t.start()
    barrier.wait()
    t0 = time.perf_counter()
    for t in threads:
        t.join()
    wall = time.perf_counter() - t0
    stats = batcher.stats()
    batcher.close()
    assert stats["answered"] == len(graphs) and stats["failed"] == 0
    return wall, np.asarray(lat), stats, out


def drive_degraded(service: LatencyService, graphs, policy: BatchPolicy,
                   fault_rate: float, seed: int = 1234,
                   window: int = WINDOW):
    """Like `drive`, but the batcher runs under a seeded chaos plan that
    fails ``fault_rate`` of flushes with a retryable E_UNAVAILABLE, and
    each client retries (bounded resubmit) until its request succeeds —
    the resilience loop a production client runs via RetryPolicy.
    Returns (wall_s, latencies, stats, reports, retries, injected)."""
    service.clear_cache()
    plan = None
    if fault_rate > 0.0:
        plan = FaultPlan(seed, [FaultSpec(site="flush", kind="error",
                                          rate=fault_rate,
                                          code=E_UNAVAILABLE,
                                          message="injected degradation",
                                          retryable=True)])
    batcher = MicroBatcher(service, policy,
                           clock=MonotonicClock(tick_s=1e-3), chaos=plan)
    index_chunks = [list(range(len(graphs)))[i::CONCURRENCY]
                    for i in range(CONCURRENCY)]
    lat = [0.0] * len(graphs)
    out = [None] * len(graphs)
    retries = [0] * CONCURRENCY
    barrier = threading.Barrier(CONCURRENCY + 1)

    def worker(tid):
        barrier.wait()
        mine = index_chunks[tid]
        for j in range(0, len(mine), window):
            futs = []
            for idx in mine[j:j + window]:
                futs.append((idx, time.perf_counter(),
                             batcher.submit(graphs[idx])))
            for idx, t0, fut in futs:
                for _attempt in range(32):      # bounded retry budget
                    try:
                        out[idx] = fut.result(60)
                        break
                    except RPCError as exc:
                        if not exc.retryable:
                            raise
                        retries[tid] += 1
                        fut = batcher.submit(graphs[idx])
                else:
                    raise AssertionError("retry budget exhausted")
                lat[idx] = time.perf_counter() - t0

    threads = [threading.Thread(target=worker, args=(i,))
               for i in range(CONCURRENCY)]
    for t in threads:
        t.start()
    barrier.wait()
    t0 = time.perf_counter()
    for t in threads:
        t.join()
    wall = time.perf_counter() - t0
    stats = batcher.stats()
    batcher.close()
    injected = plan.injected() if plan is not None else {}
    assert stats["answered"] == len(graphs), \
        "every request must eventually be answered despite injected faults"
    return wall, np.asarray(lat), stats, out, sum(retries), injected


def run(smoke: bool = False) -> None:
    # 256 distinct candidate graphs — deliberately within the process
    # feature cache (SegmentedLRUCache probation=256), so both phases
    # serve hot features and the ratio isolates what micro-batching
    # actually amortizes: per-call predictor dispatch + report assembly.
    n_requests = 256
    n_train = 8 if smoke else 12
    reps = 3                      # median-of-3 → stable on noisy runners
    graphs = [sample_architecture(1000 + s, SPACE) for s in range(n_requests)]

    # -- batched vs unbatched, numpy backend (bit-identical phases) ----------
    service = build_service(n_train, 40, backend="numpy")
    reference = {g.fingerprint(): service.predict_e2e(g) for g in graphs}

    # Warm-up pass so both phases see hot feature/fn caches.
    drive(service, graphs, BatchPolicy(max_batch=MAX_BATCH,
                                       max_wait_ticks=2, max_queue=100_000))

    trials = []
    for _ in range(reps):
        wall_u, lat_u, st_u, out_u = drive(
            service, graphs,
            BatchPolicy(max_batch=1, max_wait_ticks=0, max_queue=100_000))
        wall_b, lat_b, st_b, out_b = drive(
            service, graphs,
            BatchPolicy(max_batch=MAX_BATCH, max_wait_ticks=2,
                        max_queue=100_000))
        for out in (out_u, out_b):
            for g, rep in zip(graphs, out):
                ref = reference[g.fingerprint()]
                assert rep.fingerprint == g.fingerprint()
                assert rep.e2e_s == ref.e2e_s and rep.per_op == ref.per_op, \
                    "batched serving must be bit-identical to predict_e2e"
        trials.append((wall_u / wall_b,
                       (wall_u, lat_u, st_u), (wall_b, lat_b, st_b)))

    # Median-speedup repetition → stable numbers on noisy machines.
    trials.sort(key=lambda t: t[0])
    speedup, (wall_u, lat_u, st_u), (wall_b, lat_b, st_b) = \
        trials[len(trials) // 2]
    thr_u, thr_b = n_requests / wall_u, n_requests / wall_b
    rows = []
    for name, wall, lat, st, thr in (
            ("unbatched", wall_u, lat_u, st_u, thr_u),
            ("batched", wall_b, lat_b, st_b, thr_b)):
        rows.append({
            "phase": name,
            "requests": n_requests,
            "concurrency": CONCURRENCY,
            "wall_s": round(wall, 4),
            "req_per_s": round(thr, 1),
            "p50_ms": round(1e3 * float(np.percentile(lat, 50)), 3),
            "p99_ms": round(1e3 * float(np.percentile(lat, 99)), 3),
            "batches": st["batches"],
            "avg_batch": round(st["avg_batch"], 2),
            "max_batch": st["max_batch_observed"],
            "speedup_vs_unbatched": round(thr / thr_u, 2),
        })
    emit_csv("bench_rpc", rows)
    print(f"# batched/unbatched throughput: {speedup:.1f}x "
          f"(bit-identical reports, concurrency {CONCURRENCY})")
    assert speedup >= 5.0, \
        f"batched serving must be >=5x unbatched, got {speedup:.2f}x"

    # -- instrumentation overhead: full obs on vs quiet default --------------
    # Same batched workload with a shared Observability bundle (tracing
    # enabled, spans on every enqueue/flush/predict, shared registry)
    # versus the component-private quiet default.  The delta is what the
    # observability layer costs the hot path; it must stay under 5%.
    obs_policy = BatchPolicy(max_batch=MAX_BATCH, max_wait_ticks=2,
                             max_queue=100_000)
    traced_obs = Observability(seed=99)
    traced_svc = build_service(n_train, 40, backend="numpy", obs=traced_obs)
    traced_svc.predict_e2e(graphs[0])           # warm caches symmetrically
    obs_trials = []
    for _ in range(reps):
        wall_off, lat_off, _, _ = drive(service, graphs, obs_policy)
        wall_on, lat_on, _, _ = drive(traced_svc, graphs, obs_policy,
                                      obs=traced_obs)
        obs_trials.append((wall_on / wall_off,
                           (wall_off, lat_off), (wall_on, lat_on)))
    obs_trials.sort(key=lambda t: t[0])
    ratio, (wall_off, lat_off), (wall_on, lat_on) = \
        obs_trials[len(obs_trials) // 2]
    overhead = ratio - 1.0
    p99_off = 1e3 * float(np.percentile(lat_off, 99))
    p99_on = 1e3 * float(np.percentile(lat_on, 99))
    instrumentation = {
        "quiet_req_per_s": round(n_requests / wall_off, 1),
        "traced_req_per_s": round(n_requests / wall_on, 1),
        "overhead_frac": round(overhead, 4),
        "quiet_p99_ms": round(p99_off, 3),
        "traced_p99_ms": round(p99_on, 3),
        "p99_delta_frac": round(p99_on / p99_off - 1.0, 4),
        "spans_recorded": len(traced_obs.tracer.export()),
    }
    print(f"# instrumentation overhead: {overhead:+.1%} throughput "
          f"(p99 {p99_off:.2f} -> {p99_on:.2f} ms, tracing on)")
    assert overhead < 0.05, \
        f"metrics+tracing must cost <5% throughput, got {overhead:.1%}"

    # -- control-plane overhead: timeline sampling + alert evaluation --------
    # The closed-loop control plane (a MetricsTimeline polling registry
    # probes + an AlertEngine evaluating SLO/drift rules, exactly what
    # the recalibration autopilot's poll thread runs) samples at ~200 Hz
    # on a background thread while the batched workload runs — 10x the
    # autopilot's default 20 Hz cadence.  Its cost on the hot path must
    # stay under 5%.
    timeline = MetricsTimeline(interval=5e-3, capacity=4096)
    timeline.track_counter(traced_obs.registry, "rpc_batcher_submitted_total")
    timeline.track_quantile(traced_obs.registry, "rpc_batcher_flush_duration",
                            0.99, name="flush_p99_s")
    timeline.track("drift_score", traced_obs.drift.score)
    alert_engine = AlertEngine(timeline, [
        AlertRule("flush_slo_burn", series="flush_p99_s", threshold=0.25,
                  sustain=3),
        AlertRule("drift", series="drift_score", threshold=1.0, sustain=3),
    ], obs=traced_obs)
    ctl_stop = threading.Event()

    def control_loop():
        while not ctl_stop.is_set():
            timeline.sample()
            alert_engine.evaluate()
            ctl_stop.wait(2e-3)

    ctl_trials = []
    for _ in range(reps):
        wall_q, _, _, _ = drive(traced_svc, graphs, obs_policy,
                                obs=traced_obs)
        ctl_stop.clear()
        ctl = threading.Thread(target=control_loop, daemon=True)
        ctl.start()
        wall_ctl, _, _, _ = drive(traced_svc, graphs, obs_policy,
                                  obs=traced_obs)
        ctl_stop.set()
        ctl.join()
        ctl_trials.append((wall_ctl / wall_q, wall_q, wall_ctl))
    ctl_trials.sort(key=lambda t: t[0])
    ctl_ratio, wall_q, wall_ctl = ctl_trials[len(ctl_trials) // 2]
    ctl_overhead = ctl_ratio - 1.0
    timeline_alert = {
        "no_control_req_per_s": round(n_requests / wall_q, 1),
        "control_req_per_s": round(n_requests / wall_ctl, 1),
        "overhead_frac": round(ctl_overhead, 4),
        "timeline_samples": timeline.samples,
        "rules": len(alert_engine.rules()),
        "alerts_fired": len(alert_engine.audit.events("alert.fire")),
    }
    print(f"# timeline+alert overhead: {ctl_overhead:+.1%} throughput "
          f"({timeline.samples} samples, "
          f"{timeline_alert['alerts_fired']} fires)")
    assert ctl_overhead < 0.05, \
        f"control plane must cost <5% throughput, got {ctl_overhead:.1%}"
    assert timeline.samples > 0 and \
        alert_engine.stats()["consumed"] == timeline.samples

    # -- degraded mode: 10% of flushes fail, clients retry -------------------
    # Same batched policy, same graphs; a seeded FaultPlan fails 10% of
    # flushes with a retryable E_UNAVAILABLE and every client resubmits
    # until it succeeds.  The clean/degraded delta is the price of fault
    # recovery (wasted flush work + retry round-trips), with correctness
    # pinned: every report still bit-identical to predict_e2e.
    fault_rate = 0.10
    # Small flush cap so the fault site is exercised dozens of times per
    # run: 256 requests / max_batch=8 → >=32 flushes, and seed 1234's
    # deterministic schedule injects within the first 6 of them.
    degraded_policy = BatchPolicy(max_batch=8, max_wait_ticks=2,
                                  max_queue=100_000)
    wall_c, lat_c, _, _, _, _ = drive_degraded(
        service, graphs, degraded_policy, fault_rate=0.0)
    wall_d, lat_d, st_d, out_d, n_retries, injected = drive_degraded(
        service, graphs, degraded_policy, fault_rate=fault_rate)
    for g, rep in zip(graphs, out_d):
        ref = reference[g.fingerprint()]
        assert rep.fingerprint == g.fingerprint()
        assert rep.e2e_s == ref.e2e_s, \
            "degraded-mode answers must stay bit-identical"
    thr_c, thr_d = n_requests / wall_c, n_requests / wall_d
    degraded = {
        "fault_rate": fault_rate,
        "injected_flush_errors": injected.get("flush/error", 0),
        "client_retries": n_retries,
        "failed_attempts": st_d["failed"],
        "clean_req_per_s": round(thr_c, 1),
        "degraded_req_per_s": round(thr_d, 1),
        "clean_p99_ms": round(1e3 * float(np.percentile(lat_c, 99)), 3),
        "degraded_p99_ms": round(1e3 * float(np.percentile(lat_d, 99)), 3),
        "throughput_retained": round(thr_d / thr_c, 3),
    }
    emit_csv("bench_rpc_degraded", [degraded])
    print(f"# degraded mode ({fault_rate:.0%} flush faults): "
          f"{thr_d:.0f} req/s vs {thr_c:.0f} clean "
          f"({degraded['throughput_retained']:.0%} retained, "
          f"{n_retries} retries)")
    assert degraded["injected_flush_errors"] > 0, \
        "chaos plan must actually fire at 10% over hundreds of flushes"

    # -- auto backend under NAS-scale load -----------------------------------
    n_load = 256 if smoke else 1024
    batch_cap = 256 if smoke else 1024
    stages = 60 if smoke else 120
    auto_svc = build_service(n_train, stages, backend="auto")
    load_graphs = [sample_architecture(5000 + s, SPACE)
                   for s in range(n_load)]
    _, _, st_auto, out_auto = drive(
        auto_svc, load_graphs,
        BatchPolicy(max_batch=batch_cap, max_wait_ticks=8,
                    max_queue=100_000),
        window=16)      # deep pipelining → NAS-scale flushes
    auto_stats = auto_svc.stats()
    runs = auto_stats["backend_runs"]
    # Per-flush attribution from the batcher (rides the RPC stats path:
    # server._stats → batcher.stats()["flush_backends"]): which resolved
    # kernel actually served the flushes, not just service-wide totals.
    flush_backends = st_auto["flush_backends"]
    assert sum(flush_backends.values()) == sum(runs.values()), \
        "flush attribution must conserve the service's backend tally"
    numpy_svc = build_service(n_train, stages, backend="numpy")
    deltas = [abs(rep.e2e_s - numpy_svc.predict_e2e(g).e2e_s)
              for g, rep in zip(load_graphs[:64], out_auto[:64])]
    emit_csv("bench_rpc_auto", [{
        "requests": n_load,
        "max_batch": batch_cap,
        "gbdt_stages": stages,
        "avg_batch": round(st_auto["avg_batch"], 2),
        "backend_numpy_runs": runs.get("numpy", 0),
        "backend_jax_runs": runs.get("jax", 0),
        "flush_backends": str(flush_backends),
        "device_fused_runs": auto_stats["device_fused_runs"],
        "max_abs_delta_vs_numpy_s": float(np.max(deltas)),
    }])
    emit_bench_json("bench_rpc", {
        "smoke": smoke,
        "requests": n_load,
        "max_batch": batch_cap,
        "gbdt_stages": stages,
        "batched_speedup_vs_unbatched": round(speedup, 2),
        "backend_runs": runs,
        "flush_backends": flush_backends,
        "device_fused_runs": auto_stats["device_fused_runs"],
        "device_residency": auto_stats["device_residency"],
        "max_abs_delta_vs_numpy_s": float(np.max(deltas)),
        "degraded_mode": degraded,
        "instrumentation_overhead": instrumentation,
        "timeline_alert_overhead": timeline_alert,
    })
    if not smoke:
        assert runs.get("jax", 0) > 0, \
            "full-scale load should cross the 2^16 slot threshold"
        assert flush_backends.get("jax", 0) > 0, \
            "flush attribution should show the jax kernel serving flushes"


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--smoke", action="store_true",
                    help="CI-sized run (still asserts the 5x bar)")
    args = ap.parse_args()
    run(smoke=args.smoke)


if __name__ == "__main__":
    enable_compile_cache()
    main()
