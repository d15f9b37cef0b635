"""Chip smoke: the profile → train → serve path on a TPU, end to end.

    python chip_smoke.py              # one chip: profile, train, serve over RPC
    python chip_smoke.py --chips 4    # four chips: the row-sharded flush only

One chip (the default) drives the predictor's main path through its
public entry points:

1. profile 8 NAS graphs at 224×224 and MobileNetV2 (width 1.0, 224×224)
   with a real `ProfileSession` into a fresh `ProfileStore`, timing
   every op on the chip;
2. train a GBDT bank on the NAS graphs (MobileNetV2 is held out);
3. serve it with `LatencyRPCServer` on loopback and query it through
   `LatencyClient`: MobileNetV2's end-to-end prediction, and a
   population of 512 NAS candidates large enough that ``auto`` scores
   it on the device tier.  Every RPC report is compared with an
   in-process numpy-backend `LatencyService` on the same bank.

``--chips 4`` runs only what exists across chips: one flush of at least
65,536 rows through the row-sharded device bank, compared with the numpy
reference on the same rows.  Its bank is trained on analytic latencies
(`CostModelProfileSession`), since what it checks is the sharded flush,
not the measurements.

The script exits non-zero, printing no result line, when JAX finds no
TPU; any failed phase or check also exits non-zero.  The last line of
standard output is one JSON object naming the device.  Profile stores
and a summary go under ``chiprun_out/chip_smoke/``; the JAX compilation
cache goes where `repro.utils.compile_cache` says.
"""
from __future__ import annotations

import argparse
import json
import math
import os
import re
import sys
import time
from typing import Any, Dict, List

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))

import jax  # noqa: E402

from repro.core.dataset import synthetic_graphs  # noqa: E402
from repro.core.nas_space import NASSpaceConfig, sample_architecture  # noqa: E402
from repro.core.profiler import DeviceSetting, ProfileSession  # noqa: E402
from repro.core.realworld import mobilenet_v2  # noqa: E402
from repro.pipeline import LatencyService, PredictorHub, ProfileStore  # noqa: E402
from repro.rpc import BatchPolicy, LatencyClient, LatencyRPCServer  # noqa: E402
from repro.utils.compile_cache import enable_compile_cache  # noqa: E402

OUT_DIR = os.path.join(ROOT, "chiprun_out", "chip_smoke")
RESOLUTION = 224
N_TRAIN = 8
POPULATION = 512
SHARDED_MIN_ROWS = 65_536
# End-to-end agreement between the float32 device tier and the float64
# numpy reference: the tolerance tests/test_graph_properties.py uses.
E2E_RTOL, E2E_ATOL = 0.02, 1e-6


class CompileMeter:
    """Backend-compile seconds and persistent-cache hits, from JAX's own
    monitoring events, for the span of a ``with`` block."""

    _COMPILE = "/jax/core/compile/backend_compile_duration"

    def __init__(self) -> None:
        self.seconds = 0.0
        self.compiles = 0
        self.cache_hits = 0
        self.cache_misses = 0

    def _on_duration(self, event: str, duration: float, **_: Any) -> None:
        if event == self._COMPILE:
            self.seconds += duration
            self.compiles += 1

    def _on_event(self, event: str, **_: Any) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.cache_misses += 1

    def __enter__(self) -> "CompileMeter":
        jax.monitoring.register_event_duration_secs_listener(self._on_duration)
        jax.monitoring.register_event_listener(self._on_event)
        return self

    def __exit__(self, *exc: Any) -> None:
        jax.monitoring.unregister_event_duration_listener(self._on_duration)
        jax.monitoring.unregister_event_listener(self._on_event)

    def snapshot(self) -> Dict[str, Any]:
        return {"compile_s": self.seconds, "compiles": self.compiles,
                "cache_hits": self.cache_hits,
                "cache_misses": self.cache_misses}


class Phases:
    """Wall time and compile time of each named phase, printed as it ends."""

    def __init__(self, meter: CompileMeter) -> None:
        self.meter = meter
        self.record: Dict[str, Dict[str, Any]] = {}

    def run(self, name: str, fn, *args, **kwargs):
        before = self.meter.snapshot()
        t0 = time.perf_counter()
        out = fn(*args, **kwargs)
        wall = time.perf_counter() - t0
        after = self.meter.snapshot()
        rec = {"wall_s": wall}
        rec.update({k: after[k] - before[k] for k in after})
        self.record[name] = rec
        print(f"phase {name}: wall {wall:.3f} s, compile {rec['compile_s']:.3f} s "
              f"in {rec['compiles']} compiles, persistent-cache hits "
              f"{rec['cache_hits']} / misses {rec['cache_misses']}", flush=True)
        return out


def check(ok: bool, what: str) -> None:
    if not ok:
        raise RuntimeError(f"check failed: {what}")


def device_setting() -> DeviceSetting:
    """float32 op-by-op setting named after the local device's kind."""
    tag = re.sub(r"[^A-Za-z0-9]+", "_", jax.devices()[0].device_kind).strip("_")
    return DeviceSetting(f"{tag}_f32", "float32", "op_by_op", device=tag)


def fresh_store(name: str) -> ProfileStore:
    os.makedirs(OUT_DIR, exist_ok=True)
    path = os.path.join(OUT_DIR, name)
    if os.path.exists(path):
        os.remove(path)
    return ProfileStore(path)


def reports_agree(got, want) -> bool:
    return (got.fingerprint == want.fingerprint
            and got.num_kernels == want.num_kernels
            and [t for t, _ in got.per_op] == [t for t, _ in want.per_op]
            and abs(got.e2e_s - want.e2e_s)
            <= max(E2E_RTOL * abs(want.e2e_s), E2E_ATOL))


def profile(session: ProfileSession, graphs, setting: DeviceSetting):
    records = session.profile_suite(graphs, setting)
    check(session.measured_ops > 0, "the session measured ops on the device")
    for rec in records:
        lats = [op.latency_s for op in rec.ops]
        check(all(math.isfinite(v) and v > 0 for v in lats),
              f"{rec.name}: every op latency finite and positive")
        check(math.isfinite(rec.e2e_s) and rec.e2e_s > 0,
              f"{rec.name}: end-to-end latency finite and positive")
    return records


def serve(svc: LatencyService, holdout, population) -> Dict[str, Any]:
    """Query ``svc`` over loopback RPC; returns the reports and stats."""
    # One flush may hold the whole population, so its per-type calls
    # reach the device tier; the wait bounds how long a flush gathers.
    server = LatencyRPCServer(svc, policy=BatchPolicy(
        max_batch=len(population), max_wait_ticks=2000,
        max_queue=4 * len(population)))
    host, port = server.start()
    try:
        client = LatencyClient(host, port, timeout=600.0)
        try:
            holdout_report = client.predict_e2e(holdout)
            pop_reports = client.predict_pipelined(population)
            stats = client.stats()
        finally:
            client.close()
    finally:
        server.stop()
    return {"holdout": holdout_report, "population": pop_reports,
            "stats": stats}


def run_one_chip(phases: Phases) -> Dict[str, Any]:
    setting = device_setting()
    train = synthetic_graphs(N_TRAIN, resolution=RESOLUTION)
    holdout = mobilenet_v2(1.0, RESOLUTION)
    store = fresh_store("profile_store.jsonl")
    session = ProfileSession(store=store)
    phases.run("profile", profile, session, train + [holdout], setting)
    print(f"measured_ops: {session.measured_ops} "
          f"(graphs {session.measured_graphs}, store {store.stats()})")

    svc = phases.run("train", LatencyService.build, train + [holdout],
                     setting, session=session, predictor="gbdt",
                     train_graphs=train)
    check(session.measured_ops == store.stats()["op_records"],
          "training re-measured nothing")

    space = NASSpaceConfig(resolution=RESOLUTION)
    candidates = [sample_architecture(10_000 + i, space)
                  for i in range(POPULATION)]
    served = phases.run("serve", serve, svc, holdout, candidates)

    reference = LatencyService(svc.hub, default_setting=setting,
                               predictor="gbdt", inference_backend="numpy")
    want = phases.run("reference", reference.predict_batch,
                      [holdout] + candidates)
    got = [served["holdout"]] + served["population"]
    bad = [g.graph_name for g, w in zip(got, want) if not reports_agree(g, w)]
    check(len(got) == len(want) and not bad,
          f"RPC reports match the numpy reference (mismatches: {bad[:5]})")

    service_stats = served["stats"]["service"]
    runs = service_stats["backend_runs"]
    check(runs.get("jax", 0) > 0, f"the device tier served a flush: {runs}")
    check(service_stats["device_fused_runs"] > 0, "fused device runs > 0")

    measured = store.get_arch(setting, holdout.fingerprint()).e2e_s
    predicted = served["holdout"].e2e_s
    mape = abs(predicted - measured) / measured
    print(f"{holdout.name} e2e: measured {measured * 1e3:.6f} ms, "
          f"predicted {predicted * 1e3:.6f} ms, MAPE {100 * mape:.2f}%")
    print(f"RPC reports matching numpy reference: {len(got)}/{len(want)}")
    print(f"backend_runs: {runs}; device_fused_runs: "
          f"{service_stats['device_fused_runs']}")
    print(f"batcher: {json.dumps(served['stats']['batcher'], sort_keys=True)}")
    print(f"device_residency: "
          f"{json.dumps(service_stats['device_residency'], sort_keys=True)}")
    return {"setting": setting.name, "measured_ops": session.measured_ops,
            "holdout": {"graph": holdout.name, "measured_s": measured,
                        "predicted_s": predicted, "mape": mape},
            "reports_checked": len(got), "backend_runs": runs,
            "device_fused_runs": service_stats["device_fused_runs"],
            "device_residency": service_stats["device_residency"]}


def run_sharded(phases: Phases, *, resolution: int = RESOLUTION,
                min_rows: int = SHARDED_MIN_ROWS) -> Dict[str, Any]:
    from repro.transfer import CostModelProfileSession

    setting = DeviceSetting("cost_model_f32", "float32", "op_by_op")
    store = fresh_store("cost_model_store.jsonl")
    session = CostModelProfileSession(store=store, seed=3)
    session.profile_suite(synthetic_graphs(N_TRAIN, resolution=resolution),
                          setting)
    hub = PredictorHub()
    hub.train(store, setting, "gbdt")

    space = NASSpaceConfig(resolution=resolution)
    graphs, rows, seed = [], 0, 20_000
    while rows < min_rows:
        g = sample_architecture(seed, space)
        graphs.append(g)
        rows += len(g.nodes)
        seed += 1
    print(f"sharded flush: {len(graphs)} graphs, {rows} rows "
          f"over {len(jax.devices())} devices")

    device = LatencyService(hub, default_setting=setting, predictor="gbdt",
                            inference_backend="jax")
    got = phases.run("sharded_flush", device.predict_batch, graphs)
    res = device.device_residency()
    device.clear_cache()
    again = phases.run("sharded_flush_again", device.predict_batch, graphs)
    reference = LatencyService(hub, default_setting=setting,
                               predictor="gbdt", inference_backend="numpy")
    want = phases.run("reference", reference.predict_batch, graphs)

    bad = [g.graph_name for g, w in zip(got, want) if not reports_agree(g, w)]
    check(not bad, f"sharded reports match numpy (mismatches: {bad[:5]})")
    check(all(a.e2e_s == b.e2e_s and a.per_op == b.per_op
              for a, b in zip(got, again)), "sharded flush is deterministic")
    check(res["sharded_banks"] > 0, f"banks are row-sharded: {res}")
    check(res["bank_uploads"] == res["banks"], "each bank uploaded once")
    check(device.device_residency()["bank_uploads"] == res["bank_uploads"],
          "a second flush uploads no bank")
    print(f"sharded reports matching numpy reference: {len(got)}/{len(want)}")
    print(f"backend_runs: {device.backend_runs}")
    print(f"device_residency: {json.dumps(res, sort_keys=True)}")
    return {"graphs": len(graphs), "rows": rows,
            "backend_runs": device.backend_runs, "device_residency": res}


def main(argv: List[str]) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="1: the whole path on one chip; 4: the "
                         "row-sharded flush across four chips only")
    args = ap.parse_args(argv)

    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < args.chips:
        print(f"chip_smoke: needs {args.chips} TPU chip(s), JAX found "
              f"{len(devices)} {devices[0].platform} device(s)",
              file=sys.stderr)
        return 2
    cache_dir = enable_compile_cache()
    print(f"device: {devices[0].device_kind} x{len(devices)}; "
          f"compile cache: {cache_dir}", flush=True)

    t0 = time.perf_counter()
    with CompileMeter() as meter:
        phases = Phases(meter)
        if args.chips == 4:
            summary = run_sharded(phases)
        else:
            summary = run_one_chip(phases)
    total = meter.snapshot()
    print(f"total: wall {time.perf_counter() - t0:.3f} s, compile "
          f"{total['compile_s']:.3f} s in {total['compiles']} compiles, "
          f"persistent-cache hits {total['cache_hits']} / misses "
          f"{total['cache_misses']}")
    with open(os.path.join(OUT_DIR, f"summary_{args.chips}chip.json"),
              "w") as f:
        json.dump({"phases": phases.record, "total": total, **summary}, f,
                  indent=2, sort_keys=True, default=str)

    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
