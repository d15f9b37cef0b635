"""`OpGraph.canonical_json` against the tree it replaces.

The fingerprint's value is pinned by stored profiles, golden files and
the benchmark's independent reference, all of which hash
``json.dumps(graph.to_json(), sort_keys=True)``.  `canonical_json`
writes that text without building the tree, from a memo of params and
tensor texts; these tests hold it to the same bytes on every graph
family, on params whose equal values encode differently (``1``,
``1.0``, ``True``), and with the memo cleared at its bound.
"""
import hashlib
import json

import pytest

from repro.configs import get_arch
from repro.core import ir
from repro.core.fusion import fuse_graph
from repro.core.ir import OpGraph, TensorInfo
from repro.core.lm_lowering import ExpertShare, StepPlan, lower_step
from repro.core.nas_space import (NASSpaceConfig, RandomWiredConfig,
                                  decode_genotype, sample_architecture,
                                  sample_elastic_genotype, sample_random_wired)

SPACE = NASSpaceConfig(resolution=16)


def _reference(g: OpGraph) -> str:
    return json.dumps(g.to_json(), sort_keys=True)


def _sha16(text: str) -> str:
    return hashlib.sha256(text.encode()).hexdigest()[:16]


def _paper():
    return [sample_architecture(s, SPACE) for s in range(24)]


def _random_wired():
    rwc = RandomWiredConfig(stages=2, nodes_per_stage=8, encdec_prob=0.5)
    gs = [decode_genotype(sample_random_wired(s, rwc), SPACE)
          for s in range(12)]
    assert any(n.op_type == "resize" for g in gs for n in g.nodes)  # encdec
    return gs


def _elastic():
    return [decode_genotype(sample_elastic_genotype(s), SPACE)
            for s in range(12)]


def _diamond() -> OpGraph:
    g = OpGraph("diamond")
    x = g.add_input((1, 4, 4, 8))
    (c,) = g.add_op("conv2d", [x], [(1, 4, 4, 8)],
                    {"kernel_h": 1, "kernel_w": 1, "stride": 1, "groups": 1})
    (s,) = g.add_op("elementwise", [c], [(1, 4, 4, 8)], {"ew_kind": "sqrt"})
    (a,) = g.add_op("elementwise", [s, c], [(1, 4, 4, 8)], {"ew_kind": "add"})
    g.mark_output(a)
    return g


def _fused():
    gs = [fuse_graph(g)[1] for g in _paper()[:12] + [_diamond()]]
    fused = [f for g in gs for n in g.nodes for f in n.fused]
    assert fused and any(f.endswith("@self") for f in fused)
    return gs


def _lm_step():
    cfg = get_arch("deepseek-v2-lite")
    layers = cfg.num_layers - cfg.first_k_dense
    share = ExpertShare(held=16, group=4, popularity=tuple(
        tuple(0.05 + 0.01 * ((e + l) % 5) for e in range(16))
        for l in range(layers)))
    return [lower_step(cfg, StepPlan(decodes=(16_000, 40_000), chunk=2048,
                                     prefix=30_000, emits=True), share),
            lower_step(cfg, StepPlan(decodes=(5, 17, 131_000)), share)]


def _round_tripped():
    gs = [OpGraph.from_json(json.loads(json.dumps(g.to_json())))
          for g in _paper()]
    assert any(type(v) is list for g in gs for n in g.nodes
               for _, v in n.params)
    return gs


def _scrambled_ids():
    """Twelve tensors inserted out of order: string order ("10" < "2")
    differs from both integer and insertion order."""
    g = OpGraph("ids")
    x = g.add_input((2, 3))
    for _ in range(11):
        (x,) = g.add_op("elementwise", [x], [(2, 3)], {"ew_kind": "abs"})
    g.mark_output(x)
    g.tensors = dict(reversed(list(g.tensors.items())))
    assert max(g.tensors) >= 10
    return [g]


def _non_ascii():
    g = OpGraph("réseau-网络-☃")
    x = g.add_input((1, 8))
    (y,) = g.add_op("fully_connected", [x], [(1, 4)],
                    {"in_features": 8, "out_features": 4, "note": "ü\"\\\n"})
    g.mark_output(y)
    return [g]


FAMILIES = {
    "paper_nas": _paper,
    "random_wired_encdec": _random_wired,
    "elastic": _elastic,
    "fused": _fused,
    "lm_step": _lm_step,
    "json_round_trip": _round_tripped,
    "tensor_ids_ten_and_above": _scrambled_ids,
    "non_ascii_name": _non_ascii,
}


@pytest.mark.parametrize("family", sorted(FAMILIES))
def test_canonical_json_is_the_json_dump_byte_for_byte(family):
    graphs = FAMILIES[family]()
    for g in graphs:
        want = _reference(g)
        assert g.canonical_json() == want
        assert g.canonical_json() == want         # answered by the memo
        assert g.fingerprint() == _sha16(want)


def _one_param(value) -> OpGraph:
    g = OpGraph("trap")
    x = g.add_input((1, 8))
    (y,) = g.add_op("fully_connected", [x], [(1, 8)],
                    {"bias": value, "in_features": 8})
    g.mark_output(y)
    return g


def _one_shape(dim) -> OpGraph:
    g = _one_param(1)
    g.tensors[0] = TensorInfo((1, dim))
    return g


@pytest.mark.parametrize("make,values", [
    (_one_param, (1, 1.0, True)),
    (_one_shape, (8, 8.0)),
], ids=["param", "shape"])
def test_equal_values_of_other_types_are_not_confused(make, values):
    """``1 == 1.0 == True`` as keys, but they encode as ``1``, ``1.0``
    and ``true``: whichever the memo sees first, each graph gets its own
    fingerprint, equal to the reference's."""
    for order in (values, values[::-1]):
        ir.clear_fingerprint_memo()
        fps = {}
        for v in order:
            g = make(v)
            assert g.canonical_json() == _reference(g)
            fps[repr(v)] = g.fingerprint()
        assert len(set(fps.values())) == len(values)
        assert fps == {repr(v): _sha16(_reference(make(v))) for v in values}


def test_memo_counts_hits_and_misses():
    graphs = _paper()[:6]
    ir.clear_fingerprint_memo()
    for g in graphs:
        g.canonical_json()
    first = ir.fingerprint_memo_info()
    lookups = sum(len(g.nodes) + len(g.tensors) for g in graphs)
    assert first["hits"] + first["misses"] == lookups
    assert 0 < first["size"] <= first["misses"]
    for g in graphs:
        g.canonical_json()
    second = ir.fingerprint_memo_info()
    assert second["size"] == first["size"]
    # Only params holding tuples (encoded directly) miss again.
    direct = sum(any(type(v) is tuple for _, v in n.params)
                 for g in graphs for n in g.nodes)
    assert second["misses"] - first["misses"] == direct
    assert second["hits"] - first["hits"] == lookups - direct
    ir.clear_fingerprint_memo()
    assert ir.fingerprint_memo_info() == {"hits": 0, "misses": 0, "size": 0}


def test_memo_is_cleared_at_its_bound(monkeypatch):
    monkeypatch.setattr(ir, "MEMO_LIMIT", 16)
    ir.clear_fingerprint_memo()
    graphs = _lm_step()
    distinct = {n.params for g in graphs for n in g.nodes}
    assert len(distinct) > 16
    for g in graphs:
        assert g.canonical_json() == _reference(g)
        assert len(ir._PARAMS_TEXT) <= 16 and len(ir._TENSOR_TEXT) <= 16
    assert 0 < ir.fingerprint_memo_info()["size"] <= 32
    ir.clear_fingerprint_memo()
