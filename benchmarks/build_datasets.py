"""Build + cache the profiling datasets every benchmark reads.

Scenarios (paper §4.3: 72 scenarios across 4 phones → here, the device
axis is (dtype × executor mode) on the XLA:CPU device):
  cpu_f32  — float32, op-by-op  (mobile-CPU analogue)
  cpu_int8 — int8, op-by-op     (quantized mobile-CPU analogue)
  gpu_f32  — float32, fused     (GPU-delegate analogue: Alg C.1 groups)

Datasets: N synthetic NAS-space archs (paper's 1000, scaled for the
1-core budget) + the real-world suite (paper's 102).

  PYTHONPATH=src python -m benchmarks.build_datasets --synthetic 240
"""
from __future__ import annotations

import argparse
import os
import time

from repro.core.dataset import build_dataset, realworld_graphs, synthetic_graphs
from repro.core.profiler import DeviceSetting, ProfileSession
from repro.utils.compile_cache import enable_compile_cache
from repro.utils.logging import get_logger

log = get_logger("repro.bench.data")

SETTINGS = (
    DeviceSetting("cpu_f32", "float32", "op_by_op"),
    DeviceSetting("cpu_int8", "int8", "op_by_op"),
    DeviceSetting("gpu_f32", "float32", "fused_groups"),
)

DATA_DIR = os.path.join(os.path.dirname(__file__), "..", "reports", "datasets")


def dataset_path(kind: str, setting: str) -> str:
    return os.path.join(DATA_DIR, f"{kind}_{setting}.json")


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--synthetic", type=int, default=240)
    # int8 ops run ~5× slower on XLA:CPU (no tuned int8 GEMM — itself a
    # datapoint for the §5.2 heterogeneity story), so the non-primary
    # settings profile fewer architectures by default.
    ap.add_argument("--synthetic-int8", type=int, default=100)
    ap.add_argument("--synthetic-gpu", type=int, default=140)
    ap.add_argument("--resolution", type=int, default=64)
    ap.add_argument("--settings", default="cpu_f32,cpu_int8,gpu_f32")
    # int8 measurement is ~3.6 s/op on XLA:CPU; the real-world suite under
    # int8 is optional (only the diversity bench's int8 row uses it).
    ap.add_argument("--realworld-settings", default="cpu_f32,gpu_f32")
    args = ap.parse_args()

    os.makedirs(DATA_DIR, exist_ok=True)
    wanted = set(args.settings.split(","))
    counts = {"cpu_f32": args.synthetic, "cpu_int8": args.synthetic_int8,
              "gpu_f32": args.synthetic_gpu}
    rw = realworld_graphs(resolution=args.resolution)
    session = ProfileSession()
    for setting in SETTINGS:
        if setting.name not in wanted:
            continue
        t0 = time.time()
        syn = synthetic_graphs(counts[setting.name], resolution=args.resolution)
        build_dataset(syn, setting, dataset_path("synthetic", setting.name),
                      session=session)
        if setting.name in args.realworld_settings.split(","):
            build_dataset(rw, setting, dataset_path("realworld", setting.name),
                          session=session)
        log.info("setting %s done in %.0fs", setting.name, time.time() - t0)


if __name__ == "__main__":
    enable_compile_cache()
    main()
