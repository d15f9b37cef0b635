"""§Roofline: three-term roofline per (arch × shape × mesh) from the
dry-run JSON + analytic cost model.

Terms (TPU v5e: 197 TFLOP/s bf16, 819 GB/s HBM, ~50 GB/s/link ICI):

  compute    = FLOPs/device   / peak
  memory     = bytes/device   / HBM bw
  collective = coll bytes/dev / link bw

Two sources, both reported:
  * `hlo_*`  — compiled.cost_analysis() + HLO text (as prescribed).
    CAVEAT (measured, EXPERIMENTS §Dry-run): XLA counts each while-loop
    BODY ONCE, so scanned layer stacks and microbatch loops undercount
    by the trip count.  hlo numbers are per-program static sums.
  * `ana_*`  — analytic per-step costs from the model math (the MFU
    accounting every LLM framework uses: 6·N·D train, 2·N_active/token
    decode, + attention terms, + remat recompute, + FSDP gather traffic).
    The dominant-term analysis and MODEL_FLOPS/TOTAL ratio use these.

Usage:
  PYTHONPATH=src python -m benchmarks.roofline [--report reports/dryrun.json]
"""
from __future__ import annotations

import argparse
import json
import os
from typing import Any, Dict, List, Optional

import numpy as np

from repro.configs import ARCHS, INPUT_SHAPES, get_arch
from repro.utils.compile_cache import enable_compile_cache

PEAK_FLOPS = 197e12        # bf16 / chip
HBM_BW = 819e9             # bytes/s / chip
LINK_BW = 50e9             # bytes/s / ICI link

REPORT = os.path.join(os.path.dirname(__file__), "..", "reports", "dryrun.json")


# ---------------------------------------------------------------------------
# Analytic per-step cost model (global, then /chips)
# ---------------------------------------------------------------------------

def analytic_costs(arch: str, shape_name: str, mesh: Dict[str, int],
                   microbatches: int = 16, fsdp: Optional[bool] = None
                   ) -> Dict[str, float]:
    cfg = get_arch(arch)
    shape = INPUT_SHAPES[shape_name]
    chips = int(np.prod(list(mesh.values())))
    model_axis = mesh.get("model", 1)
    data_axis = chips // model_axis
    n = cfg.num_params()
    n_active = cfg.active_params()
    b, s = shape.global_batch, shape.seq_len
    L, d, hd = cfg.num_layers, cfg.d_model, cfg.head_dim
    heads, kvh = cfg.num_heads, cfg.num_kv_heads

    if shape.kind == "train":
        tokens = b * s
        useful = 6.0 * n_active * tokens
        # attention (causal): fwd 2·2·s²/2·h·hd per layer per seq → ×3 bwd+fwd
        attn = 0.0
        if heads:
            n_attn_layers = L if cfg.family != "hybrid" else max(1, L // max(1, cfg.shared_attn_every))
            attn = 3.0 * 2.0 * b * s * s * heads * hd * n_attn_layers
        remat = 2.0 * n_active * tokens          # one fwd recompute
        total_flops = useful + attn + remat
        # bytes: params f32 read+write + opt states + activations/microbatch
        act_bytes = 2.0 * b * s * d * L * 2 / max(1, microbatches)
        param_bytes = (4 + 4 + 4 + 4) * n        # p, g, mu, nu traffic
        total_bytes = param_bytes + act_bytes * microbatches
        # collectives: grad reduce (f32·N over data) + fsdp gathers (bf16·N)
        use_fsdp = fsdp if fsdp is not None else n >= 15e9
        coll = 4.0 * n * 2 * (data_axis - 1) / data_axis   # ring all-reduce ≈ 2N
        if use_fsdp:
            coll += 2.0 * n * microbatches                  # per-mb layer gathers
        # TP activation collectives: per layer 2 all-reduces of (b·s·d) bf16
        coll += 2.0 * 2.0 * b * s * d * L / max(1, microbatches) * 0  # overlapped in TP-seq layout
        tok_or_seq = tokens
    elif shape.kind == "prefill":
        tokens = b * s
        useful = 2.0 * n_active * tokens
        attn = 2.0 * b * s * s * heads * hd * L if heads else 0.0
        total_flops = useful + attn
        total_bytes = 2.0 * n + 2.0 * b * s * d * L
        coll = 2.0 * b * s * d * L * 2 / 4      # TP all-reduces, partial
        tok_or_seq = tokens
    else:  # decode: one token, KV cache of seq_len
        tokens = b
        useful = 2.0 * n_active * tokens
        kv_bytes = 0.0
        if kvh:
            win = cfg.sliding_window or s
            n_full = L
            if cfg.alt_local_global:
                kv_read = (min(s, cfg.sliding_window) * (L // 2) + s * (L // 2))
            elif cfg.family == "hybrid":
                kv_read = s * max(1, L // max(1, cfg.shared_attn_every))
            else:
                kv_read = s * L
            kv_bytes = 2.0 * b * kvh * hd * 2 * kv_read
        state_bytes = 0.0
        if cfg.ssm_state:
            d_inner = cfg.d_model * cfg.ssm_expand
            state_bytes = 4.0 * b * (d_inner // cfg.ssm_head_dim) * cfg.ssm_head_dim * cfg.ssm_state * L * 2
        total_flops = useful + 2.0 * kv_bytes / 2  # attn dot ≈ kv reads
        total_bytes = 2.0 * n + kv_bytes + state_bytes
        coll = 2.0 * b * d * L * 2               # TP reduces per layer
        tok_or_seq = tokens

    return {
        "ana_flops_dev": total_flops / chips,
        "ana_bytes_dev": total_bytes / chips,
        "ana_coll_dev": coll / chips,
        "model_flops": useful,
        "total_flops": total_flops,
        "useful_ratio": useful / max(total_flops, 1.0),
        "tokens": tok_or_seq,
    }


def derive_terms(rec: Dict[str, Any]) -> Dict[str, Any]:
    chips = int(np.prod(list(rec["mesh"].values())))
    ana = analytic_costs(rec["arch"], rec["shape"], rec["mesh"],
                         microbatches=rec.get("microbatches", 16),
                         fsdp=rec.get("variant") == "fsdp")
    hlo_c = rec["cost"]["flops_per_device"] / PEAK_FLOPS
    hlo_m = rec["cost"]["bytes_per_device"] / HBM_BW
    hlo_x = rec["collective_bytes"] / LINK_BW
    ana_c = ana["ana_flops_dev"] / PEAK_FLOPS
    ana_m = ana["ana_bytes_dev"] / HBM_BW
    ana_x = ana["ana_coll_dev"] / LINK_BW
    terms = {"compute": ana_c, "memory": ana_m, "collective": ana_x}
    dominant = max(terms, key=terms.get)
    step_s = max(terms.values())
    ideal_s = ana["model_flops"] / (chips * PEAK_FLOPS)
    return {
        "arch": rec["arch"], "shape": rec["shape"],
        "mesh": "x".join(str(v) for v in rec["mesh"].values()),
        "chips": chips,
        "hlo_compute_s": hlo_c, "hlo_memory_s": hlo_m, "hlo_collective_s": hlo_x,
        "ana_compute_s": ana_c, "ana_memory_s": ana_m, "ana_collective_s": ana_x,
        "dominant": dominant,
        "useful_ratio": round(ana["useful_ratio"], 3),
        "model_flops": ana["model_flops"],
        "roofline_fraction": round(ideal_s / max(step_s, 1e-30), 3),
        "hbm_gb": round(_peak_bytes(rec) / 1e9, 1),
        # TPU-corrected: minus XLA:CPU's bf16→f32 emulation buffers
        # (wrapped_convert fusions; absent on native-bf16 TPUs).
        "hbm_tpu_gb": round((_peak_bytes(rec)
                             - rec.get("cpu_upcast_bytes", 0)) / 1e9, 1),
        "fits_16gb": (_peak_bytes(rec)
                      - rec.get("cpu_upcast_bytes", 0)) <= 16e9,
    }


def _peak_bytes(rec: Dict[str, Any]) -> float:
    """arg + temp + out − alias: donated buffers (train state, KV cache)
    alias their outputs and must not be double counted."""
    m = rec["memory"]
    return (m["argument_bytes"] + m["temp_bytes"] + m["output_bytes"]
            - m["alias_bytes"])


def run(report: str = REPORT, single_pod_only: bool = True) -> List[Dict]:
    with open(report) as f:
        cells = json.load(f)["cells"]
    rows = []
    for rec in cells:
        if not rec.get("ok"):
            continue
        if single_pod_only and "pod" in rec["mesh"]:
            continue
        rows.append(derive_terms(rec))
    rows.sort(key=lambda r: (r["arch"], r["shape"]))
    from benchmarks.common import emit_csv
    display = [
        {**r,
         "ana_compute_ms": round(1e3 * r["ana_compute_s"], 3),
         "ana_memory_ms": round(1e3 * r["ana_memory_s"], 3),
         "ana_collective_ms": round(1e3 * r["ana_collective_s"], 3),
         "hlo_compute_ms": round(1e3 * r["hlo_compute_s"], 3),
         "hlo_memory_ms": round(1e3 * r["hlo_memory_s"], 3),
         "hlo_collective_ms": round(1e3 * r["hlo_collective_s"], 3)}
        for r in rows
    ]
    emit_csv("roofline", display, fieldnames=[
        "arch", "shape", "mesh", "chips",
        "ana_compute_ms", "ana_memory_ms", "ana_collective_ms",
        "hlo_compute_ms", "hlo_memory_ms", "hlo_collective_ms",
        "dominant", "useful_ratio", "roofline_fraction",
        "hbm_gb", "hbm_tpu_gb", "fits_16gb",
    ])
    return rows


if __name__ == "__main__":
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--report", default=REPORT)
    ap.add_argument("--all-meshes", action="store_true")
    a = ap.parse_args()
    run(a.report, single_pod_only=not a.all_meshes)
