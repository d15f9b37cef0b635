"""Paper Fig. 21/22 + Tables 4/5 reproduction: limited training data.

MAPE vs training-set size {30, 100, all} for each predictor, on both
synthetic test and real-world test sets.  The paper's claim: Lasso is
insensitive to training-set size and wins at 30 architectures.
"""
from __future__ import annotations

from typing import Dict, List

import numpy as np

from benchmarks.common import emit_csv, require_dataset
from repro.core.dataset import evaluate_bank, fit_predictor_bank
from repro.utils.compile_cache import enable_compile_cache

PREDICTORS = ("lasso", "rf", "gbdt", "mlp")


def run(setting: str = "cpu_f32", overhead_model: str = "affine") -> List[Dict]:
    syn = require_dataset("synthetic", setting)
    rw = require_dataset("realworld", setting)
    combined = type(syn)(syn.setting, syn.archs + rw.archs)
    n_syn = len(syn.archs)
    n_test = max(10, n_syn // 6)
    te_syn = list(range(n_syn - n_test, n_syn))
    te_rw = list(range(n_syn, len(combined.archs)))
    max_train = n_syn - n_test
    rows = []
    for n_train in (30, 100, max_train):
        tr = list(range(min(n_train, max_train)))
        for name in PREDICTORS:
            bank = fit_predictor_bank(combined, name, train_idx=tr,
                                      overhead_model=overhead_model)
            res_syn = evaluate_bank(combined, bank, te_syn)
            res_rw = evaluate_bank(combined, bank, te_rw)
            rows.append({
                "predictor": name, "n_train": len(tr),
                "synthetic_e2e_mape_pct": round(100 * res_syn["e2e_mape"], 2),
                "realworld_e2e_mape_pct": round(100 * res_rw["e2e_mape"], 2),
            })
    emit_csv("bench_limited_data", rows)
    return rows


if __name__ == "__main__":
    enable_compile_cache()
    run()
