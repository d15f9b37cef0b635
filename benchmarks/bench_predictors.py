"""Paper Fig. 14 / Table 4 reproduction: the default NAS setting.

Train/test split within the synthetic NAS dataset; all four ML
approaches; e2e MAPE + per-op-type MAPE for the dominant types.
"""
from __future__ import annotations

import time
from typing import Dict, List

import numpy as np

from benchmarks.common import emit_csv, load_dataset, require_dataset
from repro.core.dataset import evaluate_bank, fit_predictor_bank
from repro.utils.compile_cache import enable_compile_cache

PREDICTORS = ("lasso", "rf", "gbdt", "mlp")
KEY_OPS = ("conv2d", "dwconv2d", "mean", "pool_avg", "pool_max",
           "fully_connected", "elementwise")


def run(settings=("cpu_f32", "cpu_int8", "gpu_f32"),
        overhead_model: str = "affine") -> List[Dict]:
    rows = []
    for setting in settings:
        ds = load_dataset("synthetic", setting)
        if ds is None:
            continue
        n = len(ds.archs)
        n_test = max(10, n // 6)
        tr = list(range(n - n_test))
        te = list(range(n - n_test, n))
        for name in PREDICTORS:
            t0 = time.time()
            bank = fit_predictor_bank(ds, name, train_idx=tr,
                                      overhead_model=overhead_model)
            res = evaluate_bank(ds, bank, te)
            row = {
                "setting": setting, "predictor": name,
                "e2e_mape_pct": round(100 * res["e2e_mape"], 2),
                "n_train": len(tr), "n_test": len(te),
                "fit_s": round(time.time() - t0, 1),
            }
            for op in KEY_OPS:
                if op in res["per_op_mape"]:
                    row[f"{op}_mape_pct"] = round(100 * res["per_op_mape"][op], 1)
            rows.append(row)
    emit_csv("bench_predictors", rows)
    return rows


if __name__ == "__main__":
    enable_compile_cache()
    run()
