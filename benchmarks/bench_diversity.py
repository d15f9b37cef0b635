"""Paper Fig. 18 / Table 5 reproduction: neural-architecture diversity.

Train on SYNTHETIC NAS-space architectures, test on REAL-WORLD
architectures (dataset shift, paper §5.3).  The paper's headline: the
simple Lasso generalizes best under shift on CPUs.
"""
from __future__ import annotations

from typing import Dict, List

import numpy as np

from benchmarks.common import emit_csv, load_dataset, require_dataset
from repro.core.dataset import evaluate_bank, fit_predictor_bank
from repro.utils.compile_cache import enable_compile_cache

PREDICTORS = ("lasso", "rf", "gbdt", "mlp")


def run(settings=("cpu_f32", "cpu_int8", "gpu_f32"),
        overhead_model: str = "affine") -> List[Dict]:
    rows = []
    for setting in settings:
        syn = load_dataset("synthetic", setting)
        rw = load_dataset("realworld", setting)
        if syn is None or rw is None:
            continue
        # Move real-world records into the synthetic dataset's frame so
        # evaluate_bank can index them: concatenate.
        combined = type(syn)(syn.setting, syn.archs + rw.archs)
        tr = list(range(len(syn.archs)))
        te = list(range(len(syn.archs), len(combined.archs)))
        for name in PREDICTORS:
            bank = fit_predictor_bank(combined, name, train_idx=tr,
                                      overhead_model=overhead_model)
            res = evaluate_bank(combined, bank, te)
            rows.append({
                "setting": setting, "predictor": name,
                "e2e_mape_pct": round(100 * res["e2e_mape"], 2),
                "conv_mape_pct": round(100 * res["per_op_mape"].get("conv2d", np.nan), 1),
                "n_train_syn": len(tr), "n_test_rw": len(te),
            })
    emit_csv("bench_diversity", rows)
    return rows


if __name__ == "__main__":
    enable_compile_cache()
    run()
