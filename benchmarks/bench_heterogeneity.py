"""Paper Fig. 15/16 reproduction: hardware heterogeneity.

GBDT predictions across every device setting (the dtype × executor-mode
grid standing in for the paper's core-combination × dtype grid), plus
the straggler-aware serving of heterogeneous worker pools using the
predictor as the speed prior (the framework feature built on Insight 1).
"""
from __future__ import annotations

from typing import Dict, List

import numpy as np

from benchmarks.common import emit_csv, load_dataset, require_dataset
from repro.core.dataset import evaluate_bank, fit_predictor_bank
from repro.distributed.straggler import StragglerMonitor
from repro.utils.compile_cache import enable_compile_cache


def run(predictor: str = "gbdt", overhead_model: str = "affine") -> List[Dict]:
    rows = []
    for setting in ("cpu_f32", "cpu_int8", "gpu_f32"):
        ds = load_dataset("synthetic", setting)
        if ds is None:
            continue
        n = len(ds.archs)
        n_test = max(10, n // 6)
        tr, te = list(range(n - n_test)), list(range(n - n_test, n))
        bank = fit_predictor_bank(ds, predictor, train_idx=tr,
                                  overhead_model=overhead_model)
        res = evaluate_bank(ds, bank, te)
        rows.append({"name": f"{predictor}_{setting}",
                     "e2e_mape_pct": round(100 * res["e2e_mape"], 2),
                     "n_train": len(tr), "n_test": len(te)})

    # Predictor-seeded straggler planning: predict per-group step times for
    # a heterogeneous pool (one group thermally degraded 1.6x), plan
    # weighted microbatches, report predicted step-time recovery.
    ds = require_dataset("synthetic", "cpu_f32")
    base = float(np.median([a.e2e_s for a in ds.archs]))
    predicted = [base, base, base, base * 1.6]
    mon = StragglerMonitor(n_groups=4)
    mon.seed_from_predictions(predicted)
    rows.append({
        "name": "straggler_plan_speedup_equal_vs_weighted",
        "e2e_mape_pct": round(mon.predicted_speedup(16), 3),
        "n_train": 4, "n_test": 16,
    })
    emit_csv("bench_heterogeneity", rows)
    return rows


if __name__ == "__main__":
    enable_compile_cache()
    run()
