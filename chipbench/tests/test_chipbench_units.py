"""Benchmark pieces that need no chip: the drive module of a traffic
kind, the roofline count, the reference walker, the traffic digest and
the CPU refusal."""
import json
import os
import subprocess
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

from chipbench import harness, roofline, spec  # noqa: E402
from chipbench.reference import ReferenceBank, predict_graphs  # noqa: E402
from chipbench.reference_nas import FEATURES  # noqa: E402


# -- traffic kinds ----------------------------------------------------------

@pytest.mark.parametrize("name", ["search_p128", "search_p512", "search_p1024"])
def test_each_traffic_file_names_a_drive_module(name):
    kind = spec.traffic(name)["kind"]
    assert callable(harness.drive_module(kind).drive)


def test_an_unknown_traffic_kind_is_refused():
    with pytest.raises(ValueError, match="no chipbench/drive_replay.py"):
        harness.drive_module("replay")


# -- roofline ---------------------------------------------------------------

def test_traversal_work_counts_from_shapes():
    nb = roofline.bank_bytes(n_nodes=3000, n_trees=150)
    assert nb == 3000 * 20 + 150 * 4
    ops, nbytes = roofline.traversal_work(rows=1000, trees=150, depth=4,
                                          features=16, bank_nbytes=nb)
    assert ops == 1000 * 150 * 5
    assert nbytes == nb + 1000 * 16 * 4 + 1000 * 4
    peak = {"flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    assert roofline.least_time_s(ops, nbytes, peak) == pytest.approx(
        nbytes / 819e9)                             # memory bounds it
    assert roofline.least_time_s(1e15, 1.0, peak) == pytest.approx(
        1e15 / 197e12)


# -- reference --------------------------------------------------------------

def _two_tree_bank():
    # elementwise features: input_h, input_w, input_c, input_size,
    # kind_cost, n_operands.  Standardize with mean 0 / std 1 except
    # input_size (mean 1000, std 500).
    stump = [[3, 0.5, 1, 2, 0.0, False],          # input_size z <= 0.5
             [-1, 0.0, -1, -1, 1e-3, True],
             [-1, 0.0, -1, -1, 3e-3, True]]
    deep = [[2, 10.0, 1, 4, 0.0, False],          # input_c <= 10
            [4, 0.75, 2, 3, 0.0, False],          # kind_cost <= 0.75
            [-1, 0.0, -1, -1, 1e-4, True],
            [-1, 0.0, -1, -1, 2e-4, True],
            [-1, 0.0, -1, -1, 5e-4, True]]

    def tree(nodes):
        return {"max_depth": 4, "min_samples_split": 2, "min_samples_leaf": 1,
                "max_features": None, "seed": 0, "nodes": nodes}

    model = {"name": "gbdt",
             "config": {"n_stages": 2, "learning_rate": 0.5, "max_depth": 4,
                        "min_samples_split": 2, "seed": 0, "relative": True,
                        "subsample": 1.0},
             "scaler": {"mean": [0, 0, 0, 1000, 0, 0],
                        "std": [1, 1, 1, 500, 1, 1]},
             "state": {"f0": 1e-3, "trees": [tree(stump), tree(deep)]}}
    return {"setting": "x", "overhead": 2e-5, "overhead_per_kernel": 3e-6,
            "op_sum_scale": 1.05, "predictors": {"elementwise": model}}


def _graph(c, kind):
    return {"name": f"g{c}{kind}",
            "nodes": [{"op_id": 0, "op_type": "elementwise", "inputs": [0, 1],
                       "outputs": [2], "params": [["ew_kind", kind]],
                       "fused": []},
                      {"op_id": 1, "op_type": "pad", "inputs": [2],
                       "outputs": [3], "params": [], "fused": []}],
            "tensors": {"0": {"shape": [1, 8, 8, c], "dtype": "float32"},
                        "1": {"shape": [1, 8, 8, c], "dtype": "float32"},
                        "2": {"shape": [1, 8, 8, c], "dtype": "float32"},
                        "3": {"shape": [1, 10, 10, c], "dtype": "float32"}},
            "inputs": [0, 1], "outputs": [3]}


@pytest.mark.parametrize("c,kind,stump,deep", [
    (8, "neg", 1e-3, 1e-4),      # size 512: z=-0.98 left; c<=10, cost .5
    (8, "add", 1e-3, 2e-4),      # cost 1.0 > 0.75 → right of node 1
    (40, "add", 3e-3, 5e-4),     # size 2560: z=3.1 right; c>10
])
def test_reference_walks_a_hand_built_bank(c, kind, stump, deep):
    bank = ReferenceBank(_two_tree_bank())
    (rep,) = predict_graphs(bank, [_graph(c, kind)], FEATURES)
    op = 1e-3 + 0.5 * stump + 0.5 * deep
    assert rep["per_op"] == [("elementwise", pytest.approx(op)),
                             ("pad", 0.0)]        # no pad model: 0
    assert rep["num_kernels"] == 2
    assert rep["e2e_s"] == pytest.approx(2e-5 + 2 * 3e-6 + 1.05 * op)


def test_bfloat16_control_rounds_leaves():
    bank = ReferenceBank(_two_tree_bank())
    (f64,) = predict_graphs(bank, [_graph(8, "neg")], FEATURES)
    (bf,) = predict_graphs(bank, [_graph(8, "neg")], FEATURES,
                          precision="bfloat16")
    assert bf["e2e_s"] != f64["e2e_s"]
    assert bf["e2e_s"] == pytest.approx(f64["e2e_s"], rel=1e-2)


# -- traffic digest and refusal ---------------------------------------------

def _config(name):
    with open(os.path.join(ROOT, "chipbench", "configs", f"{name}.json")) as f:
        return json.load(f)


@pytest.mark.parametrize("name", ["paper_nas_224", "randwire_ws_224"])
def test_traffic_digest_guards_the_generator(name):
    from chipbench import bank
    cfg = _config(name)
    bank.check_traffic_digest(cfg)
    cfg["traffic_digest"] = "0" * 64
    with pytest.raises(RuntimeError, match="generator changed"):
        bank.check_traffic_digest(cfg)


def test_refuses_to_measure_on_a_cpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, os.path.join(ROOT, "chipbench", "run.py"),
         "--workload", "search.paper_nas_224", "--seed", "3",
         "--seconds", "1", "--trace", "0"],
        capture_output=True, text=True, timeout=120, env=env, cwd=ROOT)
    assert proc.returncode != 0
    assert '"correct"' not in proc.stdout
    assert "needs 1 TPU" in proc.stderr
