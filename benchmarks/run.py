"""Benchmark runner — one function per paper table/figure.

Prints ``name,us_per_call,derived`` style CSV per benchmark and mirrors
everything under reports/*.csv.  Requires the profiling datasets
(`python -m benchmarks.build_datasets`) and, for roofline/TPU rows, the
dry-run JSON (`python -m repro.launch.dryrun --all`).

  PYTHONPATH=src python -m benchmarks.run [--only name]
"""
from __future__ import annotations

import argparse
import time
import traceback

from repro.utils.compile_cache import enable_compile_cache

BENCHES = [
    # Pipeline rows are self-contained (no prebuilt datasets): the full
    # ProfileStore → PredictorHub → LatencyService.predict_e2e path and
    # the OpGraph adjacency-index microbenchmark.
    ("pipeline", "benchmarks.bench_pipeline"),                # docs/PIPELINE.md
    ("predict", "benchmarks.bench_predict"),                  # docs/PIPELINE.md
    ("graph_index", "benchmarks.bench_graph_index"),          # docs/PIPELINE.md
    ("transfer", "benchmarks.bench_transfer"),                # docs/PIPELINE.md
    ("search", "benchmarks.bench_search"),                    # docs/PIPELINE.md
    ("rpc", "benchmarks.bench_rpc"),                          # docs/PIPELINE.md
    ("multicore", "benchmarks.bench_multicore"),              # Fig. 2/3
    ("quantization", "benchmarks.bench_quantization"),        # Fig. 4/5
    ("fusion", "benchmarks.bench_fusion"),                    # Fig. 6/7
    ("kernel_selection", "benchmarks.bench_kernel_selection"),# Fig. 8/9, Tab. 2
    ("overhead_breakdown", "benchmarks.bench_overhead_breakdown"),  # Fig. 10/11
    ("predictors", "benchmarks.bench_predictors"),            # Fig. 14, Tab. 4
    ("heterogeneity", "benchmarks.bench_heterogeneity"),      # Fig. 15/16
    ("diversity", "benchmarks.bench_diversity"),              # Fig. 18, Tab. 5
    ("framework_opts", "benchmarks.bench_framework_opts"),    # Fig. 19/20
    ("limited_data", "benchmarks.bench_limited_data"),        # Fig. 21/22
    ("roofline", "benchmarks.roofline"),                      # §Roofline
    ("tpu_step_prediction", "benchmarks.bench_tpu_step_prediction"),  # beyond
]


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--only", default=None)
    args = ap.parse_args()
    import importlib

    failures = []
    for name, module in BENCHES:
        if args.only and args.only != name:
            continue
        t0 = time.time()
        try:
            mod = importlib.import_module(module)
            mod.run()
            print(f"# {name}: done in {time.time() - t0:.0f}s\n")
        except FileNotFoundError as e:
            print(f"# {name}: SKIPPED ({e})\n")
        except Exception:
            failures.append(name)
            print(f"# {name}: FAILED\n{traceback.format_exc()}\n")
    if failures:
        raise SystemExit(f"benchmarks failed: {failures}")


if __name__ == "__main__":
    enable_compile_cache()
    main()
