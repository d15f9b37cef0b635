"""Beyond-paper example: predict distributed TPU step latency.

The paper predicts phone inference latency without the phone; here the
same composition predicts pod step latency without the pod, from the
dry-run's compiled artifacts + the analytic cost model, for any
assigned (arch × shape).

  PYTHONPATH=src python examples/predict_tpu_step.py --arch qwen2-72b
"""
import argparse
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

from benchmarks.roofline import HBM_BW, LINK_BW, PEAK_FLOPS, analytic_costs
from repro.configs import ARCHS, INPUT_SHAPES, shape_applicable, get_arch
from repro.utils.compile_cache import enable_compile_cache


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen2-72b")
    args = ap.parse_args()
    mesh = {"data": 16, "model": 16}
    cfg = get_arch(args.arch)
    print(f"{args.arch} on a v5e {mesh} mesh (256 chips):")
    for sname, shape in INPUT_SHAPES.items():
        ok, why = shape_applicable(cfg, shape)
        if not ok:
            print(f"  {sname:12s} skipped: {why.split(';')[0]}")
            continue
        ana = analytic_costs(args.arch, sname, mesh)
        terms = {
            "compute": ana["ana_flops_dev"] / PEAK_FLOPS,
            "memory": ana["ana_bytes_dev"] / HBM_BW,
            "collective": ana["ana_coll_dev"] / LINK_BW,
        }
        dom = max(terms, key=terms.get)
        step = max(terms.values())
        tput = ana["tokens"] / step
        print(f"  {sname:12s} step ≈ {1e3*step:9.2f} ms  "
              f"[{dom}-bound]  ≈ {tput:,.0f} tok/s")


if __name__ == "__main__":
    enable_compile_cache()
    main()
