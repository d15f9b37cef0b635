"""The comparison that decides ``correct``, driven through a whole run
on the CPU at a small size: sound runs pass, and the bfloat16 control
and each fault planted in the timed path fail, the four-chip one on
four virtual CPU devices."""
import json
import os
import subprocess
import sys
import textwrap

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

from chipbench import bank, compare, harness, spec  # noqa: E402
from chipbench.reference import ReferenceBank  # noqa: E402

PEAK = {"flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
# 60 trees a type: a 128-candidate generation sends conv2d's ~1,400
# rows over the 2^16-slot line, so the fused device path runs.
STAGES = 60


@pytest.fixture(scope="module")
def setup(tmp_path_factory):
    bench = spec.benchmark()
    cfg = spec.config(bench, "paper_nas_224")
    cfg["bank"]["hparams"]["n_stages"] = STAGES
    root = str(tmp_path_factory.mktemp("hub"))
    hub, _ = bank.train_hub(cfg, root)
    search = dict(spec.traffic("search_p512"), population=128, children=128,
                  cycle_generations=2)
    return {"cfg": cfg, "hub": hub, "ref": ReferenceBank.load(
        bank.bank_file(root)), "features": spec.reference_features(cfg),
        "search": search,
        "workdir": str(tmp_path_factory.mktemp("work"))}


def _run(setup, seconds):
    import jax

    out = harness.execute(setup["cfg"], setup["search"], seed=2**31 + 11,
                          seconds=seconds, trace=False, t_start=0.0,
                          devices=jax.devices(), workdir=setup["workdir"],
                          peak=PEAK, hub=setup["hub"])
    run = out["run"]
    numbers = compare.readings(setup["ref"], setup["features"],
                               out["answers"], unanswered=run.failed)
    return out, numbers, compare.judge(numbers, setup["cfg"]["limits"])


def _fused_fault(kind, hits):
    from repro.kernels import tree_gather

    original = tree_gather.fused_predict

    def faulty(flat, thr, red, x):
        hits.append(len(x))
        if kind == "altered_answer":
            out = original(flat, thr, red, x)
            out[0] += 1e-3
            return out
        half = original(flat, thr, red, x[: len(x) // 2])
        return np.concatenate([half, np.full(len(x) - len(half), half.mean())])

    return tree_gather, "fused_predict", faulty


def _numpy_fault(kind, hits):
    from repro.core.predictors.flat import FlatEnsemble

    original = FlatEnsemble._predict_trees_np

    def faulty(self, x):
        hits.append(len(x))
        if kind == "altered_answer":
            out = original(self, x)
            out[0] = out[0] * 2.0 + 1e-3
            return out
        half = original(self, x[: max(1, len(x) // 2)])
        rest = np.repeat(half.mean(axis=0, keepdims=True),
                         len(x) - len(half), axis=0)
        return np.concatenate([half, rest])

    return FlatEnsemble, "_predict_trees_np", faulty


def test_search_run_is_correct_and_its_control_is_not(setup):
    out, numbers, ok = _run(setup, 0.5)
    assert ok, numbers
    assert out["run"].cands > 0 and numbers["e2e_gap"] < 1e-5
    control = compare.readings(setup["ref"], setup["features"],
                               out["answers"], precision="bfloat16",
                               against="control")
    assert not compare.judge(control, setup["cfg"]["limits"]), control


@pytest.mark.parametrize("tier,fault", [
    ("fused", "altered_answer"), ("fused", "half_batch"),
    ("numpy", "altered_answer"), ("numpy", "half_batch")])
def test_a_planted_fault_reads_not_correct(setup, monkeypatch, tier, fault):
    """Both tiers answer in a search window: op types with fewer row x
    tree slots than the device line score on numpy."""
    hits = []
    plant = _fused_fault if tier == "fused" else _numpy_fault
    monkeypatch.setattr(*plant(fault, hits))
    _, numbers, ok = _run(setup, 0.5)
    assert hits, "the fault was never on the timed path"
    assert not ok, numbers


_CROSS_CHIP = textwrap.dedent("""
    import json, os, sys
    sys.path[:0] = [{root!r}, os.path.join({root!r}, "src")]
    import jax
    import numpy as np
    from chipbench import bank, compare, harness, spec
    from chipbench.reference import ReferenceBank
    from repro.kernels import tree_gather

    fault = sys.argv[1]
    if fault == "exchange_left_out":
        original = tree_gather.DeviceBank.fused

        def fused(self, *args):
            out = np.array(original(self, *args))
            if self.mesh is not None and len(out) >= tree_gather.SHARD_MIN_ROWS:
                # Each chip keeps its own shard; without the exchange the
                # host sees chip 0's rows in every chip's place.
                n = len(out) // self.mesh.devices.size
                out = np.tile(out[:n], self.mesh.devices.size)
            return out

        tree_gather.DeviceBank.fused = fused
    cfg = spec.config(spec.benchmark(), "paper_nas_224")
    cfg["bank"]["hparams"]["n_stages"] = {stages}
    root = {workdir!r}
    hub, _ = bank.train_hub(cfg, os.path.join(root, "hub"))
    search = dict(spec.traffic("search_p1024"), population=128, children=128,
                  cycle_generations=2)
    out = harness.execute(cfg, search, seed=2**31 + 3, seconds=0.5,
                          trace=False, t_start=0.0, devices=jax.devices(),
                          workdir=root, peak={peak!r}, hub=hub)
    numbers = compare.readings(ReferenceBank.load(bank.bank_file(hub.root)),
                               spec.reference_features(cfg), out["answers"])
    sharded = hub.banks and sum(
        m.flat()._device_bank is not None and m.flat()._device_bank.mesh
        is not None for b in hub.banks.values() for m in b.predictors.values())
    print(json.dumps({{"ok": compare.judge(numbers, cfg["limits"]),
                       "numbers": numbers, "sharded": int(sharded)}}))
""")


@pytest.mark.parametrize("fault", ["none", "exchange_left_out"])
def test_four_chip_flush_without_its_exchange_reads_not_correct(
        tmp_path, fault):
    code = _CROSS_CHIP.format(root=ROOT, stages=STAGES, workdir=str(tmp_path),
                              peak=PEAK)
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    proc = subprocess.run([sys.executable, "-c", code, fault],
                          capture_output=True, text=True, timeout=300,
                          env=env, cwd=ROOT)
    assert proc.returncode == 0, proc.stderr[-3000:]
    out = json.loads(proc.stdout.strip().splitlines()[-1])
    assert out["sharded"] > 0
    assert out["ok"] is (fault == "none"), out["numbers"]
