"""One run of one cell: set-up, window, trace, comparison, result line.

The harness is driven by data: the cell names a configuration file and
a traffic file, and BENCHMARK.json lists the metrics, each computed by
its own reader in ``metrics/``.  Three modules plug in by name
(`chipbench.spec`):

* the traffic file's ``kind`` names the drive module
  ``chipbench/drive_<kind>.py``, whose one entry,
  ``drive(cfg, spec, *, hub, obs, seed, seconds, on_window)``, returns
  the run record and the window's answers;
* the configuration's ``graphs`` (``nas`` where it has none) names its
  graph source ``chipbench/graphs_<name>.py``: the graphs the bank is
  profiled on, and the generator that the traffic digest guards;
* the same name selects its reference table
  ``chipbench/reference_<name>.py``: the op features the comparison
  computes.  Set-up stops, before the window, on an op type of those
  graphs that the table lacks.
"""
from __future__ import annotations

import gc
import json
import os
import shutil
import sys
import tempfile
from typing import Any, Dict, List, Optional

from chipbench import bank as bankmod
from chipbench import compare, spec
from chipbench.meter import CompileMeter, GcMeter
from chipbench.record import Run
from chipbench.reference import ReferenceBank

# Finished spans a traced run keeps (the program's default is 4,096).
SPAN_CAPACITY = 2_000_000


def _bank_shapes(hub: Any) -> Dict[str, Dict[str, int]]:
    """Per op type: trees, depth, features, nodes of the served bank."""
    from chipbench.roofline import bank_bytes

    out = {}
    for bank in hub.banks.values():
        for op_type, model in bank.predictors.items():
            flat = model.flat()
            out[op_type] = {"trees": flat.n_trees,
                            "depth": max(1, flat.max_depth),
                            "features": len(model.scaler.mean),
                            "bank_bytes": bank_bytes(flat.n_nodes,
                                                     flat.n_trees)}
    return out


def drive_module(kind: str) -> Any:
    """``chipbench.drive_<kind>``, the module that drives a traffic kind."""
    return spec.plugin("drive", kind, "traffic kind")


def execute(cfg: Dict[str, Any], tspec: Dict[str, Any], *, seed: int,
            seconds: float, trace: bool, t_start: float, devices: List[Any],
            workdir: str, peak: Dict[str, Any],
            hub: Optional[Any] = None) -> Dict[str, Any]:
    """Run a cell's set-up and window; returns the run record, the
    window's (graph, report) pairs and the saved bank's path.  A
    ``hub`` trained earlier by `bank.train_hub` for the same
    configuration is reused (the control runs several seeds in one
    process)."""
    from repro.obs import Observability
    from repro.utils.compile_cache import enable_compile_cache

    from chipbench.trace import DeviceTracer

    driver = drive_module(tspec["kind"])
    bankmod.check_traffic_digest(cfg)
    enable_compile_cache()
    obs = Observability(tracing=trace, span_capacity=SPAN_CAPACITY)
    if hub is None:
        hub, _ = bankmod.train_hub(cfg, os.path.join(workdir, "hub"))
    tracer = DeviceTracer(os.path.join(workdir, "trace")) if trace else None
    marks: Dict[str, Any] = {}

    with CompileMeter() as meter, GcMeter() as gcm:
        def on_window(opening: bool) -> None:
            if opening:
                # Frozen, the heap that set-up leaves (JAX, the bank, the
                # warm-up's graphs) is not walked again by the window's
                # full collections, so the window's collector time does
                # not depend on how much set-up allocated.  The window's
                # own objects are collected as before.
                gc.collect()
                gc.freeze()
                if tracer is not None:
                    tracer.start()
                marks["compile"] = meter.snapshot()
                marks["reg"] = obs.registry.snapshot(include_collected=False)
                gcm.active = True
            else:
                gcm.active = False
                after = meter.snapshot()
                marks["compile"] = {k: after[k] - marks["compile"][k]
                                    for k in after}
                marks["reg_after"] = obs.registry.snapshot(
                    include_collected=False)
                if tracer is not None:
                    tracer.stop()

        run, answers = driver.drive(cfg, tspec, hub=hub, obs=obs, seed=seed,
                                    seconds=seconds, on_window=on_window)

    run.setup_s = run.t0 - t_start
    run.chips = len(devices)
    run.compile = marks["compile"]
    run.notes["gc_in_window"] = gcm.summary()
    run.reg_before, run.reg_after = marks["reg"], marks["reg_after"]
    run.spans = obs.tracer.export()
    run.bank_shapes = _bank_shapes(hub)
    run.peak = peak
    run.memory_peak_bytes = max(
        (d.memory_stats() or {}).get("peak_bytes_in_use", 0) for d in devices)
    if tracer is not None:
        run.trace = tracer.reduce(run.t0, run.t1)
    return {"run": run, "answers": answers,
            "bank_file": bankmod.bank_file(hub.root)}


def metrics(bench: Dict[str, Any], cell_name: str, run: Run,
            trace: bool) -> Dict[str, Dict[str, Any]]:
    section = "per_layer" if trace else "end_to_end"
    out = {}
    for m in spec.cell_metrics(bench, cell_name, section):
        value = spec.reader(m["name"])(run)
        if value is not None:
            out[m["name"]] = {"value": value, "unit": m["unit"]}
    return out


def run_and_print(bench: Dict[str, Any], cell: Dict[str, Any], *, seed: int,
                  seconds: float, trace: bool, t_start: float,
                  devices: List[Any]) -> int:
    seed = int(seed) % (1 << 64)
    cfg = spec.config(bench, cell["config"])
    tspec = spec.traffic(cell["traffic"])
    workdir = tempfile.mkdtemp(prefix="chipbench-")
    try:
        out = execute(cfg, tspec, seed=seed, seconds=seconds, trace=trace,
                      t_start=t_start, devices=devices, workdir=workdir,
                      peak=spec.peaks(devices[0].device_kind))
        run = out["run"]
        run.cell = cell["name"]
        numbers = compare.readings(
            ReferenceBank.load(out["bank_file"]),
            spec.reference_features(cfg), out["answers"],
            unanswered=run.failed)
    finally:
        shutil.rmtree(workdir, ignore_errors=True)
    limits = cfg["limits"]
    correct = compare.judge(numbers, limits)
    result: Dict[str, Any] = {
        "correct": correct,
        "attempted": run.attempted,
        "failed": run.failed,
        "metrics": metrics(bench, cell["name"], run, trace),
        "device": {"platform": devices[0].platform,
                   "kind": devices[0].device_kind,
                   "count": len(devices),
                   "memory_peak_bytes": run.memory_peak_bytes},
    }
    if trace and run.trace is not None:
        result["device"]["busy_s"] = run.trace.busy_s
        result["device"]["window_s"] = run.window_s
        result["breakdown"] = run.trace.breakdown(run.spans,
                                                  run.annotations)
    result["checks"] = {k: {"value": numbers[k], "limit": limits[k]}
                        for k in compare.NUMBERS}
    for k, v in sorted(run.notes.items()):
        print(f"note {k}: {v}", file=sys.stderr)
    print(f"compile in window: {json.dumps(run.compile)}", file=sys.stderr)
    for k in compare.NUMBERS:
        print(f"check {k}: {numbers[k]!r} (limit {limits[k]!r})",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0
