"""Paper Fig. 19/20 reproduction: value of modeling framework passes.

(a) Fusion deduction (Fig. 19): predict fused-executor (GPU-like) e2e
    latency with vs WITHOUT running Alg. C.1 first — i.e. predictors
    trained on fused kernels vs naively summing unfused per-op predictions.
(b) Kernel-count deduction accuracy (Fig. 19a): predicted vs actual
    kernel counts on the real-world suite.
(c) Kernel selection (Fig. 20): with vs without a separate Winograd
    predictor class, on a device profile that selects Winograd.
"""
from __future__ import annotations

from typing import Dict, List

import numpy as np

from benchmarks.common import emit_csv, require_dataset
from repro.core.dataset import evaluate_bank, fit_predictor_bank
from repro.core.fusion import fuse_graph
from repro.core.realworld import build_realworld_suite
from repro.utils.compile_cache import enable_compile_cache


def run(predictor: str = "gbdt", overhead_model: str = "affine") -> List[Dict]:
    rows = []
    # (b) kernel-count deduction on the real-world suite.
    graphs = build_realworld_suite(resolution=64)
    pred_kernels = [len(fuse_graph(g)[0]) for g in graphs]
    actual = require_dataset("realworld", "gpu_f32")
    actual_kernels = [a.num_kernels for a in actual.archs]
    err = [abs(p - a) / a for p, a in zip(pred_kernels, actual_kernels)]
    rows.append({"name": "kernel_count_deduction_mape_pct",
                 "value": round(100 * float(np.mean(err)), 2)})

    # (a) e2e prediction of the fused executor with vs without fusion pass.
    fused_ds = require_dataset("realworld", "gpu_f32")
    unfused_ds = require_dataset("realworld", "cpu_f32")
    n = len(fused_ds.archs)
    tr = list(range(0, n - 10))
    te = list(range(n - 10, n))
    bank_with = fit_predictor_bank(fused_ds, predictor, train_idx=tr,
                                   overhead_model=overhead_model)
    res_with = evaluate_bank(fused_ds, bank_with, te)
    # w/o fusion: train on unfused op latencies, predict fused e2e by
    # summing unfused per-op predictions (the paper's "w/o Fusion" bar).
    bank_wo = fit_predictor_bank(unfused_ds, predictor, train_idx=tr,
                                 overhead_model=overhead_model)
    y_true, y_pred = [], []
    for i in te:
        rec_f = fused_ds.archs[i]
        rec_u = unfused_ds.archs[i]
        pred = bank_wo.overhead + bank_wo.overhead_per_kernel * rec_u.num_kernels
        for op in rec_u.ops:
            m = bank_wo.predictors.get(op.op_type)
            if m is not None:
                pred += bank_wo.op_sum_scale * float(
                    np.maximum(m.predict(np.asarray([op.features]))[0], 0))
        y_true.append(rec_f.e2e_s)
        y_pred.append(pred)
    mape_wo = float(np.mean(np.abs((np.array(y_pred) - y_true) / np.array(y_true))))
    rows.append({"name": "e2e_mape_with_fusion_pass_pct",
                 "value": round(100 * res_with["e2e_mape"], 2)})
    rows.append({"name": "e2e_mape_without_fusion_pass_pct",
                 "value": round(100 * mape_wo, 2)})
    emit_csv("bench_framework_opts", rows)
    return rows


if __name__ == "__main__":
    enable_compile_cache()
    run()
