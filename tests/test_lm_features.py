"""Features of the latent-attention and routed-expert ops, and their
routing through a bank, on 16 step graphs of the DeepSeek-V2-Lite
deployment at its published widths.

* The program's featurizers (`repro.core.features`) and the benchmark's
  independent table (`chipbench/reference_mla_moe.py`) give equal
  vectors for every op.
* The numpy tier and the device tier route every row to the same leaf
  of every tree, under the bank `chipbench.bank.train_hub` builds for
  the configuration.  FLOP and byte features reach about 10^13, far
  above 2^24, where float32 no longer holds every integer: the device
  compares float32 features with float32 cut-offs
  (`tree_gather.raw_thresholds`), the host standardized float64.
"""
import os
import sys

import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from chipbench import bank, spec  # noqa: E402
from chipbench.reference import Op  # noqa: E402

from repro.core.features import featurize, graph_features  # noqa: E402
from repro.kernels import tree_gather as tg  # noqa: E402

NEW_TYPES = {"mla_prefill", "mla_decode", "moe_router", "moe_routed"}


@pytest.fixture(scope="module")
def cfg():
    return spec.config(spec.benchmark(), "deepseek_v2_lite_ep4")


@pytest.fixture(scope="module")
def graphs(cfg):
    return bank.sample_graphs(cfg, np.random.default_rng(11), 16)


def test_program_and_reference_features_are_equal(cfg, graphs):
    table = spec.reference_features(cfg)
    seen = set()
    for g in graphs:
        gj = g.to_json()
        for node, nj in zip(g.nodes, gj["nodes"]):
            _, got = featurize(g, node)
            want = np.asarray(table[node.op_type](Op(gj, nj)), np.float64)
            assert np.array_equal(got, want), (node.op_type, got, want)
            seen.add(node.op_type)
    assert NEW_TYPES <= seen


def test_decode_and_routed_features_carry_cache_and_load(graphs):
    g = next(g for g in graphs if g.op_type_counts().get("mla_decode"))
    dec = next(n for n in g.nodes if n.op_type == "mla_decode")
    names, vals = featurize(g, dec)
    f = dict(zip(names, vals))
    assert f["kv_max"] <= f["kv_total"] and f["seqs"] >= 1
    assert f["cache_bytes"] == 2 * f["kv_total"] * (512 + 64)
    assert f["flops"] > 1e9
    moe = next(n for n in g.nodes if n.op_type == "moe_routed")
    names, vals = featurize(g, moe)
    f = dict(zip(names, vals))
    assert f["experts_held"] == 16 and 0 < f["max_load"] <= f["routed"]
    assert f["active"] <= 16


def test_numpy_and_device_tiers_route_every_row_alike(cfg, graphs,
                                                      tmp_path):
    hub, _ = bank.train_hub(cfg, str(tmp_path))
    (models,) = [b.predictors for b in hub.banks.values()]
    big = 0
    for op_type, model in models.items():
        x = np.concatenate([graph_features(g).matrix[op_type]
                            for g in graphs
                            if op_type in graph_features(g).matrix])
        big += int((np.abs(x) >= 2 ** 24).sum())
        flat = model.flat()
        host = flat.predict_trees(model.scaler.transform(x))
        db = flat.device_bank()
        raw = tg.raw_thresholds(flat, model.scaler)
        dev = np.asarray(tg._traverse(
            db.feature, raw, db.left, db.right, db.value, db.roots,
            jnp.asarray(x.astype(np.float32)), depth=db.depth))
        differ = int((host.astype(np.float32) != dev).sum())
        assert differ == 0, (op_type, differ, host.size)
    assert big > 0
