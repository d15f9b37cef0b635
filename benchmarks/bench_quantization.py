"""Paper Fig. 4/5 reproduction: int8 quantization effects.

Measures per-op-type speedup of int8 over float32 (Fig. 5) and
end-to-end speedup (Fig. 4) on the CPU device.  The paper's mobile
result: conv/FC speed up; element-wise/pad DEGRADE (rescale overhead).
On XLA:CPU conv may not speed up (no tuned int8 GEMM) — reported as
measured; the element-wise degradation structure transfers.
"""
from __future__ import annotations

from collections import defaultdict
from typing import Dict, List

import numpy as np

from repro.utils.compile_cache import enable_compile_cache
from benchmarks.common import emit_csv, require_dataset


def run() -> List[Dict]:
    f32 = require_dataset("synthetic", "cpu_f32")
    i8 = require_dataset("synthetic", "cpu_int8")
    # Per-op: match records by signature position (same graphs, same order).
    speedups: Dict[str, List[float]] = defaultdict(list)
    e2e = []
    for a32, a8 in zip(f32.archs, i8.archs):
        e2e.append(a32.e2e_s / a8.e2e_s)
        for o32, o8 in zip(a32.ops, a8.ops):
            assert o32.op_type == o8.op_type
            speedups[o32.op_type].append(o32.latency_s / max(o8.latency_s, 1e-12))
    rows = [{
        "name": "e2e",
        "median_speedup_f32_over_int8_inv": round(float(np.median(e2e)), 3),
        "mean": round(float(np.mean(e2e)), 3),
        "n": len(e2e),
    }]
    for t, v in sorted(speedups.items()):
        rows.append({
            "name": t,
            "median_speedup_f32_over_int8_inv": round(float(np.median(v)), 3),
            "mean": round(float(np.mean(v)), 3),
            "n": len(v),
        })
    emit_csv("bench_quantization", rows)
    return rows


if __name__ == "__main__":
    enable_compile_cache()
    run()
