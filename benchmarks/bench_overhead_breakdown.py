"""Paper Fig. 10/11 reproduction: overhead gap + latency breakdown.

(a) e2e − Σ(per-op) gap distribution per setting (Fig. 10) — on
    XLA:CPU the sync-dispatch gap is small/positive, the stream-dispatch
    (GPU-like) gap is negative (async overlap);
(b) per-op-type share of e2e latency (Fig. 11 / 13).
"""
from __future__ import annotations

from collections import defaultdict
from typing import Dict, List

import numpy as np

from repro.utils.compile_cache import enable_compile_cache
from benchmarks.common import emit_csv, load_dataset, require_dataset


def run() -> List[Dict]:
    rows = []
    for setting in ("cpu_f32", "cpu_int8", "gpu_f32"):
        ds = load_dataset("synthetic", setting)
        if ds is None:
            continue
        gaps = [(a.e2e_s - a.op_sum_s) / a.e2e_s for a in ds.archs]
        rows.append({
            "name": f"overhead_gap_{setting}",
            "median_pct_of_e2e": round(100 * float(np.median(gaps)), 2),
            "q1": round(100 * float(np.percentile(gaps, 25)), 2),
            "q3": round(100 * float(np.percentile(gaps, 75)), 2),
        })
        share: Dict[str, List[float]] = defaultdict(list)
        for a in ds.archs:
            tot = max(a.op_sum_s, 1e-12)
            by_type: Dict[str, float] = defaultdict(float)
            for o in a.ops:
                by_type[o.op_type] += o.latency_s
            for t, v in by_type.items():
                share[t].append(v / tot)
        for t in sorted(share):
            rows.append({
                "name": f"latency_share_{setting}_{t}",
                "median_pct_of_e2e": round(100 * float(np.median(share[t])), 2),
                "q1": round(100 * float(np.percentile(share[t], 25)), 2),
                "q3": round(100 * float(np.percentile(share[t], 75)), 2),
            })
    emit_csv("bench_overhead_breakdown", rows)
    return rows


if __name__ == "__main__":
    enable_compile_cache()
    run()
