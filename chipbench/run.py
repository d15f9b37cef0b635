"""Run one cell of the benchmark once and print its result line.

    python3 chipbench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell is looked up in BENCHMARK.json.  The run refuses, exiting
non-zero with no result line, when JAX finds no TPU or fewer chips than
the cell asks for.  Its last standard-output line is one JSON object:
``correct``, ``attempted``, ``failed``, ``metrics`` (the cell's
end-to-end metrics, or with ``--trace 1`` its per-layer metrics),
``device``, with ``--trace 1`` ``breakdown``, and last ``checks``: each
number the correctness comparison read, beside its limit.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]


def main(argv):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    from chipbench import spec
    bench = spec.benchmark()
    cell = spec.cell(bench, args.workload)

    spec.use_checkout_cache()
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < cell["chips"]:
        print(f"chipbench: cell {cell['name']} needs {cell['chips']} TPU "
              f"chip(s); JAX found {len(devices)} {devices[0].platform} "
              f"device(s)", file=sys.stderr)
        return 2

    from chipbench import harness
    return harness.run_and_print(bench, cell, seed=args.seed,
                                 seconds=args.seconds, trace=bool(args.trace),
                                 t_start=T_START,
                                 devices=devices[:cell["chips"]])


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
