"""Paper Fig. 2/3 reproduction: multi-worker scheduling effects.

This container has one CPU core, so (per DESIGN.md §2) the multi-core
study transplants to the straggler MODEL over measured single-worker op
latencies: equal-split (TFLite behaviour) vs weighted-split (our
planner) across homogeneous and heterogeneous worker sets, using real
per-op measurements from the profiling dataset.

Reproduced phenomena:
  * sublinear homogeneous speedup (only conv/dwconv/FC parallelize);
  * heterogeneous DEGRADATION: fast+slow < fast alone under equal split
    (paper's counterintuitive Fig. 2 result);
  * the weighted planner recovers the loss (beyond-paper fix).
"""
from __future__ import annotations

from typing import Dict, List

import numpy as np

from benchmarks.common import emit_csv, require_dataset
from repro.core.distributed_model import (
    Worker, graph_latency_multiworker, speedup_curve,
)
from repro.utils.compile_cache import enable_compile_cache


def run() -> List[Dict]:
    ds = require_dataset("synthetic", "cpu_f32")
    rows = []
    # average over a sample of architectures
    sample = ds.archs[:40]
    curves = []
    for rec in sample:
        ops = [(o.op_type, o.latency_s) for o in rec.ops]
        curves.append(speedup_curve(ops, [1, 2, 3, 4], sync_overhead=2e-5))
    for k in (1, 2, 3, 4):
        vals = [c[k] for c in curves]
        rows.append({"name": f"homogeneous_{k}core_speedup",
                     "median": round(float(np.median(vals)), 3),
                     "q1": round(float(np.percentile(vals, 25)), 3),
                     "q3": round(float(np.percentile(vals, 75)), 3)})

    # Heterogeneous: fast (1.0) + slow (0.4) vs fast alone — equal split.
    degr, fixed = [], []
    for rec in sample:
        ops = [(o.op_type, o.latency_s) for o in rec.ops]
        fast = graph_latency_multiworker(ops, [Worker("f", 1.0)])
        mixed_eq = graph_latency_multiworker(
            ops, [Worker("f", 1.0), Worker("s", 0.4)], policy="equal")
        mixed_wt = graph_latency_multiworker(
            ops, [Worker("f", 1.0), Worker("s", 0.4)], policy="weighted")
        degr.append(mixed_eq / fast)
        fixed.append(mixed_wt / fast)
    rows.append({"name": "hetero_equal_split_vs_fast_alone(>1=worse)",
                 "median": round(float(np.median(degr)), 3),
                 "q1": round(float(np.percentile(degr, 25)), 3),
                 "q3": round(float(np.percentile(degr, 75)), 3)})
    rows.append({"name": "hetero_weighted_split_vs_fast_alone(<1=better)",
                 "median": round(float(np.median(fixed)), 3),
                 "q1": round(float(np.percentile(fixed, 25)), 3),
                 "q3": round(float(np.percentile(fixed, 75)), 3)})
    emit_csv("bench_multicore", rows)
    return rows


if __name__ == "__main__":
    enable_compile_cache()
    run()
