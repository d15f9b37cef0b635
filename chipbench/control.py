"""Readings that set a cell's correctness limits.

    python3 chipbench/control.py --workload search.paper_nas_224 \
        --seeds 1,2,3 --seconds 5

For each seed, in one process (one bank), runs the cell's set-up and a
short window on the chip, then prints one JSON line with two sets of
numbers (`chipbench.compare`): the program's reports against the
float64 reference (the lower readings), and the control, the reference
computed in bfloat16 and put in the program's place (the upper
readings).  A limit lies between the largest lower and the smallest
upper reading.  The benchmark's own runs never call this.
"""
import argparse
import json
import os
import shutil
import sys
import tempfile
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

CONTROL_PRECISION = "bfloat16"


def readings(bench, cell, seeds, seconds, devices, peak):
    """Yield (seed, program numbers, control numbers, run) per seed."""
    from chipbench import bank, compare, harness, spec
    from chipbench.reference import ReferenceBank

    cfg = spec.config(bench, cell["config"])
    tspec = spec.traffic(cell["traffic"])
    workdir = tempfile.mkdtemp(prefix="chipbench-control-")
    try:
        hub, _ = bank.train_hub(cfg, os.path.join(workdir, "hub"))
        ref = ReferenceBank.load(bank.bank_file(hub.root))
        features = spec.reference_features(cfg)
        for seed in seeds:
            out = harness.execute(cfg, tspec, seed=seed, seconds=seconds,
                                  trace=False, t_start=time.perf_counter(),
                                  devices=devices, workdir=workdir, peak=peak,
                                  hub=hub)
            run = out["run"]
            prog = compare.readings(ref, features, out["answers"],
                                    unanswered=run.failed)
            ctl = compare.readings(ref, features, out["answers"],
                                   precision=CONTROL_PRECISION,
                                   against="control")
            yield seed, prog, ctl, run
    finally:
        shutil.rmtree(workdir, ignore_errors=True)


def main(argv):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=5.0)
    args = ap.parse_args(argv)

    from chipbench import spec
    spec.use_checkout_cache()
    import jax
    from repro.utils.compile_cache import enable_compile_cache

    devices = jax.devices()
    bench = spec.benchmark()
    cell = spec.cell(bench, args.workload)
    if devices[0].platform != "tpu" or len(devices) < cell["chips"]:
        print("control: needs the cell's TPU chips", file=sys.stderr)
        return 2
    enable_compile_cache()
    devices = devices[:cell["chips"]]
    peak = spec.peaks(devices[0].device_kind)
    seeds = [int(s) for s in args.seeds.split(",")]
    for seed, prog, ctl, run in readings(bench, cell, seeds, args.seconds,
                                         devices, peak):
        print(json.dumps({"seed": seed, "program": prog, "control": ctl,
                          "attempted": run.attempted, "failed": run.failed,
                          "compile_in_window": run.compile}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
