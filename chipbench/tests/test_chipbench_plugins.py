"""A configuration's graph source and reference table plug in by name.

The NAS configurations take ``graphs_nas.py`` and ``reference_nas.py``
without naming them, and their reference reproduces, bit for bit, what
it gave for a recorded draw before the table became an argument.  A
configuration of LM step graphs (test-only modules registered under
the names the lookup imports) goes through set-up, the program's
service and the comparison with no file of the harness edited; a
planted fault in its table reads not correct, and an op type missing
from its table stops the run at set-up, before any window.
"""
import gzip
import importlib.util
import json
import os
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

from chipbench import (bank, compare, graphs_nas, harness,  # noqa: E402
                       reference_nas, spec)
from chipbench.reference import ReferenceBank, predict_graphs  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
PEAK = {"flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
NAS = ("paper_nas_224", "randwire_ws_224")


def _load(name):
    mod_spec = importlib.util.spec_from_file_location(
        f"chipbench_test_{name}", os.path.join(HERE, f"{name}.py"))
    mod = importlib.util.module_from_spec(mod_spec)
    mod_spec.loader.exec_module(mod)
    return mod


def _execute(cfg, tspec, workdir, hub=None, seconds=0.2):
    import jax

    return harness.execute(cfg, tspec, seed=2**31 + 5, seconds=seconds,
                           trace=False, t_start=0.0, devices=jax.devices(),
                           workdir=str(workdir), peak=PEAK, hub=hub)


# -- the NAS configurations ---------------------------------------------------

@pytest.mark.parametrize("name", NAS)
def test_nas_reference_reproduces_the_recorded_draw(tmp_path, name):
    """32 graphs of seed 7 through the bank `train_hub` builds; the
    reports were recorded with the conv table inside `reference.py`."""
    with gzip.open(os.path.join(HERE, "data",
                                "reference_seed7.json.gz")) as f:
        want = json.load(f)[name]
    cfg = spec.config(spec.benchmark(), name)
    assert spec.graph_source(cfg) is graphs_nas
    assert spec.reference_features(cfg) is reference_nas.FEATURES
    hub, _ = bank.train_hub(cfg, str(tmp_path))
    graphs = [g.to_json() for g in
              bank.sample_graphs(cfg, np.random.default_rng(7), 32)]
    got = predict_graphs(ReferenceBank.load(bank.bank_file(hub.root)),
                         graphs, spec.reference_features(cfg))
    assert json.loads(json.dumps(got)) == want


@pytest.mark.parametrize("missing", ["graphs", "reference"])
def test_an_unknown_plug_in_name_stops_set_up(tmp_path, monkeypatch,
                                              missing):
    cfg = dict(spec.config(spec.benchmark(), "paper_nas_224"), graphs="nope")
    if missing == "reference":
        monkeypatch.setitem(sys.modules, "chipbench.graphs_nope", graphs_nas)
    with pytest.raises(ValueError, match=f"no chipbench/{missing}_nope.py"):
        _execute(cfg, spec.traffic("search_p128"), tmp_path)


# -- a configuration of LM step graphs ----------------------------------------

LM = {
    "name": "lm_toy",
    "graphs": "lm_toy",
    "setting": {"name": "cost_model_f32", "dtype": "float32",
                "mode": "op_by_op"},
    "bank": {"profile_seed": 3, "train_seed": 5, "train_graphs": 24,
             "predictor": "gbdt",
             "hparams": {"n_stages": 40, "learning_rate": 0.1,
                         "max_depth": 4, "min_samples_split": 2},
             "fit_seed": 0, "overhead_model": "affine"},
    "limits": {"e2e_gap": 1e-4, "op_gap": 1e-4, "structure": 0,
               "unanswered": 0},
}
LM_TRAFFIC = {"kind": "lm_toy", "graphs": 48}


@pytest.fixture(scope="module")
def lm(tmp_path_factory):
    graphs, ref = _load("lm_toy_graphs"), _load("lm_toy_reference")
    with pytest.MonkeyPatch.context() as mp:
        mp.setitem(sys.modules, "chipbench.graphs_lm_toy", graphs)
        mp.setitem(sys.modules, "chipbench.drive_lm_toy", graphs)
        mp.setitem(sys.modules, "chipbench.reference_lm_toy", ref)
        cfg = dict(LM, traffic_digest=bank.traffic_digest(LM))
        hub, _ = bank.train_hub(cfg, str(tmp_path_factory.mktemp("hub")))
        yield {"cfg": cfg, "hub": hub, "graphs": graphs, "ref": ref,
               "bank": ReferenceBank.load(bank.bank_file(hub.root))}


def _lm_run(lm, workdir):
    out = _execute(lm["cfg"], LM_TRAFFIC, workdir, hub=lm["hub"])
    numbers = compare.readings(lm["bank"], spec.reference_features(lm["cfg"]),
                               out["answers"], unanswered=out["run"].failed)
    return out, numbers, compare.judge(numbers, lm["cfg"]["limits"])


def test_lm_graphs_go_through_set_up_the_service_and_the_comparison(
        lm, tmp_path):
    assert set(lm["hub"].banks[next(iter(lm["hub"].banks))].predictors) == {
        "matmul", "attention", "norm", "moe_gmm"}
    out, numbers, ok = _lm_run(lm, tmp_path)
    assert len(out["answers"]) >= LM_TRAFFIC["graphs"]
    assert numbers["structure"] == 0 and numbers["unanswered"] == 0
    assert numbers["e2e_gap"] < 1e-6, numbers
    assert ok, numbers


def test_a_planted_fault_in_the_lm_reference_table_reads_not_correct(
        lm, tmp_path, monkeypatch):
    matmul = lm["ref"].FEATURES["matmul"]

    def doubled_flops(op):
        vals = matmul(op)
        return vals[:-1] + [2.0 * vals[-1]]

    monkeypatch.setitem(lm["ref"].FEATURES, "matmul", doubled_flops)
    _, numbers, ok = _lm_run(lm, tmp_path)
    assert not ok, numbers


@pytest.mark.parametrize("stage", ["generator", "training"])
def test_an_op_type_missing_from_the_reference_stops_set_up(
        lm, tmp_path, monkeypatch, stage):
    windows = []
    drive = lm["graphs"].drive

    def recording_drive(*args, **kwargs):
        windows.append(kwargs["seed"])
        return drive(*args, **kwargs)

    monkeypatch.setattr(lm["graphs"], "drive", recording_drive)
    monkeypatch.delitem(lm["ref"].FEATURES, "moe_gmm")
    with pytest.raises(RuntimeError, match="lm_toy: op type.s. moe_gmm "
                       f"of its {stage}"):
        if stage == "generator":
            _execute(lm["cfg"], LM_TRAFFIC, tmp_path, hub=lm["hub"])
        else:
            bank.train_hub(lm["cfg"], str(tmp_path))
    assert windows == []
