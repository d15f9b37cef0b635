"""Property-fuzz layer over the graph pipeline (hypothesis; skip-clean).

Seeded strategies draw (a) random-wired/elastic genotypes through the
real samplers and (b) wilder arbitrary-fanout DAGs than any generator
emits (duplicate operands, diamonds, multi-output heads), then assert
the invariants the rest of the stack leans on:

  * fusion conserves ops — every original op lands in exactly one
    group, one fused node per group, no dangling tensor references;
  * fusion introduces no cycles (`validate` re-checks topo order);
  * fused latency ≤ sum of parts under the roofline cost model
    (element-wise tails add no flops, merged bytes never exceed the
    parts, each merge saves one kernel launch);
  * `fuse_graph` is idempotent — re-fusing a fused graph is a no-op;
  * featurize→predict parity: the "jax" service backend (float32 trees)
    agrees with "numpy" (float64 trees) within tolerance.

Profiles: "dev" (default, small; keeps tier-1 fast) and "ci"
(HYPOTHESIS_PROFILE=ci; ≥500 examples total, derandomized so a CI
failure reproduces locally with the same seed).
"""
import json
import os

import numpy as np
import pytest

hypothesis = pytest.importorskip("hypothesis")
from hypothesis import HealthCheck, given, settings
from hypothesis import strategies as st

from repro.core.cost_model import graph_cost
from repro.core.dataset import synthetic_graphs
from repro.core.fusion import fuse_graph
from repro.core.ir import OpGraph
from repro.core.nas_space import (NASSpaceConfig, RandomWiredConfig,
                                  RandomWiredGenotype, decode_genotype,
                                  sample_elastic_genotype, sample_random_wired)
from repro.core.profiler import DeviceSetting
from repro.pipeline import LatencyService, PredictorHub, ProfileStore
from repro.search import SupernetQuality, grow, mutate, repair, shrink
from repro.transfer import CostModelProfileSession

settings.register_profile(
    "dev", max_examples=10, derandomize=True, deadline=None,
    suppress_health_check=[HealthCheck.too_slow])
settings.register_profile(
    "ci", max_examples=80, derandomize=True, deadline=None,
    suppress_health_check=[HealthCheck.too_slow])
settings.load_profile(os.environ.get("HYPOTHESIS_PROFILE", "dev"))

SOURCE = DeviceSetting("cpu_f32", "float32", "op_by_op")
SPACE = NASSpaceConfig(resolution=16)


# ---------------------------------------------------------------------------
# Strategies
# ---------------------------------------------------------------------------

@st.composite
def rw_genotypes(draw):
    """Random-wired genotypes through the real seeded samplers."""
    seed = draw(st.integers(0, 2**31 - 1))
    cfg = RandomWiredConfig(
        model=draw(st.sampled_from(("ws", "er", "ba"))),
        stages=draw(st.integers(1, 2)),
        nodes_per_stage=draw(st.integers(3, 7)),
        stem_c=8, channel_scale=0.25,
        encdec_prob=1.0 if draw(st.booleans()) else 0.0)
    return sample_random_wired(seed, cfg)


_EW_UNARY = ("sqrt", "abs", "square")
_EW_BINARY = ("add", "mul", "maximum")


@st.composite
def wild_graphs(draw):
    """Arbitrary-fanout DAGs, wilder than any generator: every op is
    shape-preserving so any tensor can feed any later op — including
    the same tensor twice into one binop (the diamond-collapse case)."""
    g = OpGraph("fuzz")
    c = draw(st.sampled_from((4, 8)))
    shape = (1, 8, 8, c)
    tensors = [g.add_input(shape)]
    n_ops = draw(st.integers(2, 10))
    for _ in range(n_ops):
        kind = draw(st.sampled_from(("conv", "dw", "unary", "binary", "act")))
        src = tensors[draw(st.integers(0, len(tensors) - 1))]
        if kind == "conv":
            (y,) = g.add_op("conv2d", [src], [shape],
                            {"kernel_h": 3, "kernel_w": 3, "stride": 1,
                             "groups": 1, "act": None, "padding": "SAME"})
        elif kind == "dw":
            (y,) = g.add_op("dwconv2d", [src], [shape],
                            {"kernel_h": 3, "kernel_w": 3, "stride": 1,
                             "act": None, "padding": "SAME"})
        elif kind == "unary":
            (y,) = g.add_op("elementwise", [src], [shape],
                            {"ew_kind": draw(st.sampled_from(_EW_UNARY))})
        elif kind == "binary":
            rhs = tensors[draw(st.integers(0, len(tensors) - 1))]
            (y,) = g.add_op("elementwise", [src, rhs], [shape],
                            {"ew_kind": draw(st.sampled_from(_EW_BINARY))})
        else:
            (y,) = g.add_op("activation", [src], [shape],
                            {"act": draw(st.sampled_from(("relu", "sigmoid")))})
        tensors.append(y)
    consumed = {t for n in g.nodes for t in n.inputs}
    for t in tensors[1:]:
        if t not in consumed:
            g.mark_output(t)
    g.validate()
    return g


# ---------------------------------------------------------------------------
# Fusion invariants
# ---------------------------------------------------------------------------

@given(g=wild_graphs())
def test_fusion_conserves_nodes_and_edges(g):
    groups, fused = fuse_graph(g)
    # Every original op in exactly one group; one fused node per group.
    member_ids = sorted(oid for gr in groups for oid in gr.op_ids)
    assert member_ids == sorted(n.op_id for n in g.nodes)
    assert len(member_ids) == len(set(member_ids))
    assert len(fused.nodes) == len(groups)
    # No dangling tensor references, and the graph interface survives.
    produced = set(fused.input_ids)
    for n in fused.nodes:
        produced.update(n.outputs)
    for n in fused.nodes:
        assert set(n.inputs) <= produced
    assert set(fused.output_ids) <= produced
    assert fused.output_ids == g.output_ids


@given(g=wild_graphs())
def test_fusion_introduces_no_cycles(g):
    _, fused = fuse_graph(g)
    fused.validate()   # re-checks topological order == acyclicity


@given(g=wild_graphs())
def test_fused_latency_at_most_sum_of_parts(g):
    before = graph_cost(g)
    _, fused = fuse_graph(g)
    after = graph_cost(fused)
    assert after["latency_s"] <= before["latency_s"] * (1 + 1e-12) + 1e-15


def _structure(g):
    """Name-free structural identity (fuse_graph re-suffixes the name)."""
    return ([(n.op_id, n.op_type, n.inputs, n.outputs, n.params, n.fused)
             for n in g.nodes], g.input_ids, g.output_ids)


@given(g=wild_graphs())
def test_fusion_is_idempotent(g):
    _, once = fuse_graph(g)
    _, twice = fuse_graph(once)
    assert _structure(twice) == _structure(once)


# ---------------------------------------------------------------------------
# Genotype properties
# ---------------------------------------------------------------------------

@given(gt=rw_genotypes())
def test_random_wired_decode_roundtrip_deterministic(gt):
    g1 = decode_genotype(gt, SPACE)           # validates internally
    clone = RandomWiredGenotype.from_json(json.loads(json.dumps(gt.to_json())))
    assert clone == gt and clone.digest() == gt.digest()
    assert decode_genotype(clone, SPACE).fingerprint() == g1.fingerprint()


@given(gt=rw_genotypes(), seed=st.integers(0, 2**31 - 1),
       n=st.integers(1, 6))
def test_random_wired_mutation_chain_stays_canonical(gt, seed, n):
    rng = np.random.default_rng(seed)
    for _ in range(n):
        gt = mutate(gt, rng, SPACE)
    assert repair(gt, SPACE) == gt
    decode_genotype(gt, SPACE)   # still decodes + validates


@given(seed=st.integers(0, 2**31 - 1), step=st.integers(0, 2**31 - 1))
def test_supernet_quality_monotone_under_shrink_grow(seed, step):
    gt = sample_elastic_genotype(seed, SPACE)
    q = SupernetQuality(seed=1)
    base = q(gt)
    # Same rng seed → shrink/grow hit the same (block, knob) site.
    shrunk = shrink(gt, np.random.default_rng(step), SPACE)
    grown = grow(gt, np.random.default_rng(step), SPACE)
    assert q(shrunk) <= base + 1e-12
    assert q(grown) >= base - 1e-12


# ---------------------------------------------------------------------------
# Featurize → predict parity across service backends
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def backend_services():
    store = ProfileStore()
    session = CostModelProfileSession(store=store, seed=3)
    for g in synthetic_graphs(8, resolution=16):
        session.profile_graph(g, SOURCE)
    hub = PredictorHub()
    hub.train(store, SOURCE, "gbdt", hparams={"n_stages": 20}, min_samples=3)
    return {b: LatencyService(hub, default_setting=SOURCE, predictor="gbdt",
                              inference_backend=b)
            for b in ("numpy", "jax")}


@given(gt=rw_genotypes())
@settings(suppress_health_check=[HealthCheck.too_slow,
                                 HealthCheck.function_scoped_fixture])
def test_predict_parity_across_backends(backend_services, gt):
    g = decode_genotype(gt, SPACE)
    e2e = {b: svc.predict_batch([g])[0].e2e_s
           for b, svc in backend_services.items()}
    # numpy trees run in f64; near-tie splits may route differently, so
    # the end-to-end sums agree within tolerance rather than bitwise.
    assert e2e["numpy"] == pytest.approx(e2e["jax"], rel=0.02, abs=1e-6)
