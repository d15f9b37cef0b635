"""Beyond-paper: the paper's technique pointed at the TPU framework.

Per-op latency predictors trained on analytic-cost labels of LM ops
(matmul/attention/moe/ssd/norm), then composed to predict distributed
step latency for the assigned architectures — validated against the
roofline-derived step estimates from the dry-run artifacts.  This is
§4's "predict without deploying" with (phone → pod) swapped in.
"""
from __future__ import annotations

import json
import os
from typing import Dict, List

import numpy as np

from benchmarks.common import emit_csv
from benchmarks.roofline import REPORT, analytic_costs, PEAK_FLOPS, HBM_BW, LINK_BW
from repro.configs import ARCHS, INPUT_SHAPES
from repro.core.cost_model import op_cost
from repro.core.ir import OpGraph
from repro.core.predictors import make_predictor
from repro.utils.compile_cache import enable_compile_cache


def _lm_op_dataset(n: int = 400, seed: int = 0):
    """Synthetic LM-op configs labeled by the analytic TPU cost model."""
    rng = np.random.default_rng(seed)
    xs, ys = [], []
    for _ in range(n):
        g = OpGraph("lm")
        m = int(rng.choice([128, 512, 2048, 8192]))
        k = int(rng.choice([512, 1024, 4096, 8192]))
        nn = int(rng.choice([512, 2048, 8192, 29568]))
        t0 = g.add_input((m, k), "bfloat16")
        (t1,) = g.add_op("matmul", [t0], [(m, nn)],
                         {"m": m, "n": nn, "k": k, "batch": 1}, out_dtype="bfloat16")
        g.mark_output(t1)
        node = g.nodes[0]
        from repro.core.features import featurize
        names, vals = featurize(g, node)
        xs.append(vals)
        ys.append(op_cost(g, node).total_s)
    return np.asarray(xs), np.asarray(ys)


def run() -> List[Dict]:
    rows = []
    # 1. Validate the predictor pipeline on LM ops (cost-model labels).
    x, y = _lm_op_dataset()
    for name in ("lasso", "gbdt"):
        m = make_predictor(name)
        m.fit(x[:320], y[:320])
        rows.append({"name": f"lm_matmul_op_{name}_mape_pct",
                     "value": round(100 * m.mape(x[320:], y[320:]), 2)})

    # 2. Step-latency estimates per assigned arch on the production mesh,
    #    from the same three-term composition the roofline uses.
    if os.path.exists(REPORT):
        with open(REPORT) as f:
            cells = json.load(f)["cells"]
        for rec in cells:
            if not rec.get("ok") or "pod" in rec["mesh"]:
                continue
            ana = analytic_costs(rec["arch"], rec["shape"], rec["mesh"],
                                 microbatches=rec.get("microbatches", 16),
                                 fsdp=rec.get("variant") == "fsdp")
            step = max(ana["ana_flops_dev"] / PEAK_FLOPS,
                       ana["ana_bytes_dev"] / HBM_BW,
                       ana["ana_coll_dev"] / LINK_BW)
            tput = ana["tokens"] / max(step, 1e-12)
            rows.append({
                "name": f"step_{rec['arch']}_{rec['shape']}",
                "value": round(1e3 * step, 3),  # ms
                "tokens_per_s": f"{tput:.3g}",
            })
    emit_csv("bench_tpu_step_prediction", rows,
             fieldnames=["name", "value", "tokens_per_s"])
    return rows


if __name__ == "__main__":
    enable_compile_cache()
    run()
