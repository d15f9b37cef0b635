"""`repro.search` — predictor-in-the-loop NAS engine.

Covers: genotype sampling/decoding equivalence with the legacy
sample-only path, seeded determinism of mutate/crossover and whole
search runs, Pareto-front invariants (non-domination, bounded pruning,
crowding tie-breaks), checkpoint/resume bit-equivalence, the
one-predict_batch-per-device-per-generation contract, and multi-device
constraint filtering against a transfer-calibrated synthetic second
device.  Everything runs on the deterministic cost-model session — no
wall-clock measurement anywhere.
"""
import json
import os

import numpy as np
import pytest

from repro.core.dataset import synthetic_graphs
from repro.core.nas_space import (Genotype, NASSpaceConfig,
                                  RandomWiredConfig, RandomWiredGenotype,
                                  decode_genotype, genotype_from_json,
                                  genotype_from_rng, sample_architecture,
                                  sample_elastic_genotype, sample_genotype,
                                  sample_random_wired)
from repro.core.profiler import DeviceSetting
from repro.pipeline import LatencyService, PredictorHub, ProfileStore
from repro.search import (DeviceBudget, LatencyScorer, ParetoFront,
                          SearchConfig, SearchEngine, crossover,
                          crowding_distance, dominates, graph_flops,
                          graph_params, grow, make_quality, mutate,
                          mutate_elastic, nondominated_rank, random_genotype,
                          repair, shrink)
from repro.search.encoding import decode
from repro.transfer import (CostModelProfileSession, ReplayProfileSession,
                            SyntheticDevice, TransferEngine)

SOURCE = DeviceSetting("cpu_f32", "float32", "op_by_op")
TARGET = DeviceSetting("sim", "float32", "op_by_op", device="sim")
SPACE = NASSpaceConfig(resolution=16)


@pytest.fixture(scope="module")
def served():
    """Cost-model-profiled store + trained hub + service (+ budgets)."""
    store = ProfileStore()
    session = CostModelProfileSession(store=store, seed=3)
    graphs = synthetic_graphs(8, resolution=16)
    for g in graphs:
        session.profile_graph(g, SOURCE)
    hub = PredictorHub()
    hub.train(store, SOURCE, "gbdt", hparams={"n_stages": 20}, min_samples=3)
    svc = LatencyService(hub, default_setting=SOURCE, predictor="gbdt")
    e2e = [store.get_arch(SOURCE, g.fingerprint()).e2e_s for g in graphs]
    return {"store": store, "hub": hub, "service": svc,
            "budget_s": float(np.median(e2e))}


def small_config(**kw) -> SearchConfig:
    base = dict(population_size=12, generations=4, children_per_gen=10,
                tournament_size=4, seed=11, resolution=16, front_capacity=8)
    base.update(kw)
    return SearchConfig(**base)


# ---------------------------------------------------------------------------
# Genotype encoding: sampling parity, operators, repair
# ---------------------------------------------------------------------------

class TestEncoding:
    def test_sample_decode_matches_sample_architecture(self):
        for seed in (0, 7, 42):
            legacy = sample_architecture(seed, SPACE)
            gt = sample_genotype(seed, SPACE)
            again = decode_genotype(gt, SPACE, name=f"nas_{seed}")
            assert legacy.fingerprint() == again.fingerprint()

    def test_sampled_genotypes_are_canonical(self):
        for seed in range(20):
            gt = sample_genotype(seed, SPACE)
            assert repair(gt, SPACE) == gt

    def test_genotype_json_roundtrip_and_digest(self):
        gt = sample_genotype(3, SPACE)
        clone = Genotype.from_json(json.loads(json.dumps(gt.to_json())))
        assert clone == gt
        assert clone.digest() == gt.digest()

    def test_mutate_deterministic_per_seed(self):
        gt = sample_genotype(1, SPACE)
        a = mutate(gt, np.random.default_rng(5), SPACE)
        b = mutate(gt, np.random.default_rng(5), SPACE)
        assert a == b
        # A stream of mutations from one generator explores (digests vary).
        rng = np.random.default_rng(5)
        digests = {mutate(gt, rng, SPACE).digest() for _ in range(10)}
        assert len(digests) > 3

    def test_crossover_deterministic_and_blockwise(self):
        a, b = sample_genotype(1, SPACE), sample_genotype(2, SPACE)
        c1 = crossover(a, b, np.random.default_rng(9), SPACE)
        c2 = crossover(a, b, np.random.default_rng(9), SPACE)
        assert c1 == c2
        # Each block comes from one of the parents (up to context repair,
        # which may reset a group count the child's channels invalidate).
        for i, gene in enumerate(c1.blocks):
            assert (gene.kind, gene.out_c) in {
                (a.blocks[i].kind, a.blocks[i].out_c),
                (b.blocks[i].kind, b.blocks[i].out_c)}
        assert c1.head_c in (a.head_c, b.head_c)

    def test_mutation_chain_decodes_valid(self):
        # Long chains of edits (incl. kind flips + channel changes that
        # invalidate downstream groups/splits) must always decode.
        rng = np.random.default_rng(0)
        gt = random_genotype(rng, SPACE)
        for _ in range(60):
            gt = mutate(gt, rng, SPACE)
            g = decode(gt, SPACE)          # validate() runs inside
            assert g.num_ops() > 10

    def test_repair_fixes_stale_groups(self):
        gt = sample_genotype(4, SPACE)
        # Force an invalid group count onto the second block.
        from dataclasses import replace
        bad = gt.replace_block(1, replace(gt.blocks[1], kind="conv", groups=7))
        fixed = repair(bad, SPACE)
        in_c = fixed.blocks[0].out_c
        g = fixed.blocks[1].groups
        assert g == 1 or (in_c % g == 0 and fixed.blocks[1].out_c % g == 0)
        decode(fixed, SPACE)

    def test_quality_proxies(self):
        g = sample_architecture(0, SPACE)
        assert graph_flops(g) > 0
        assert graph_params(g) > 0
        assert make_quality("flops")(g) == pytest.approx(
            np.log(graph_flops(g)))
        assert make_quality("balanced")(g) < make_quality("flops")(g)
        with pytest.raises(ValueError):
            make_quality("nope")


# ---------------------------------------------------------------------------
# Pareto front invariants
# ---------------------------------------------------------------------------

class TestPareto:
    def test_no_dominated_member(self):
        rng = np.random.default_rng(0)
        front = ParetoFront()
        for i, p in enumerate(rng.random((200, 3))):
            front.add(f"k{i}", p)
        pts = front.objectives()
        for i in range(len(pts)):
            for j in range(len(pts)):
                if i != j:
                    assert not dominates(pts[i], pts[j])

    def test_add_semantics(self):
        front = ParetoFront()
        assert front.add("a", [1.0, 1.0])
        assert not front.add("b", [2.0, 2.0])       # dominated → rejected
        assert not front.add("dup", [1.0, 1.0])     # duplicate point
        assert front.add("c", [0.5, 2.0])           # trade-off admitted
        assert front.add("d", [0.5, 0.5])           # dominates a and c
        assert [k for k, _, _ in front.members()] == ["d"]

    def test_readd_with_changed_objectives_keeps_invariant(self):
        front = ParetoFront()
        assert front.add("d1", [1.0, 1.0])
        assert front.add("d3", [0.5, 2.0])          # trade-off member
        # Re-scoring d1 to a dominated point must re-run admission, not
        # silently overwrite — else the front holds a dominated member.
        assert not front.add("d1", [3.0, 3.0])
        assert [k for k, _, _ in front.members()] == ["d3"]
        # Re-scoring to a dominating point evicts the rest.
        assert front.add("d1", [0.1, 0.1])
        assert [k for k, _, _ in front.members()] == ["d1"]

    def test_capacity_prunes_least_crowded_keeps_extremes(self):
        front = ParetoFront(capacity=4)
        # A clean 1D trade-off line; "c2" sits in the densest region.
        pts = {"a": (0.0, 1.0), "b": (1.0, 0.0), "c1": (0.45, 0.55),
               "c2": (0.5, 0.5), "c3": (0.55, 0.45)}
        for k, p in pts.items():
            front.add(k, p)
        keys = {k for k, _, _ in front.members()}
        assert len(keys) == 4
        assert {"a", "b"} <= keys                   # extremes survive

    def test_crowding_tie_break_stable(self):
        # Equal-crowding interior points: pruning must pick the same
        # victim regardless of insertion order (digest tie-break).
        pts = {"a": (0.0, 1.0), "b": (1.0, 0.0),
               "m1": (0.25, 0.75), "m2": (0.5, 0.5), "m3": (0.75, 0.25)}
        orders = [list(pts), list(reversed(list(pts)))]
        survivors = []
        for order in orders:
            front = ParetoFront(capacity=4)
            for k in order:
                front.add(k, pts[k])
            survivors.append(sorted(k for k, _, _ in front.members()))
        assert survivors[0] == survivors[1]

    def test_front_json_roundtrip(self):
        front = ParetoFront(capacity=8)
        rng = np.random.default_rng(1)
        for i, p in enumerate(rng.random((50, 2))):
            front.add(f"k{i}", p)
        clone = ParetoFront.from_json(json.loads(json.dumps(front.to_json())))
        assert front.digest_equal(clone)

    def test_rank_and_crowding_shapes(self):
        pts = np.array([[0.0, 1.0], [1.0, 0.0], [0.5, 0.5], [1.0, 1.0]])
        ranks = nondominated_rank(pts)
        assert list(ranks[:3]) == [0, 0, 0] and ranks[3] == 1
        crowd = crowding_distance(pts[:3])
        assert np.isinf(crowd[0]) and np.isinf(crowd[1])


# ---------------------------------------------------------------------------
# Search runs: determinism, contract, resume, constraints
# ---------------------------------------------------------------------------

class TestSearchEngine:
    def test_run_deterministic_across_invocations(self, served):
        budgets = [DeviceBudget(SOURCE, served["budget_s"])]
        cfg = small_config()
        r1 = SearchEngine(served["service"], budgets, cfg).run()
        r2 = SearchEngine(served["service"], budgets, cfg).run()
        assert r1.front_json() == r2.front_json()
        assert [s.to_json() for s in r1.stats] == [s.to_json() for s in r2.stats]
        assert len(r1.front) > 0
        assert r1.candidates_scored >= cfg.population_size

    def test_one_predict_batch_per_generation_per_device(self, served,
                                                         monkeypatch):
        svc = served["service"]
        budgets = [DeviceBudget(SOURCE, served["budget_s"])]
        cfg = small_config(seed=23)
        calls = {"n": 0}
        real = svc.predict_batch

        def counting(*a, **kw):
            calls["n"] += 1
            return real(*a, **kw)

        monkeypatch.setattr(svc, "predict_batch", counting)
        eng = SearchEngine(svc, budgets, cfg)
        rep = eng.run()
        gens_with_new = sum(1 for s in rep.stats if s.new_scored > 0)
        assert calls["n"] == gens_with_new * len(budgets)
        assert rep.predict_batch_calls == calls["n"]
        for s in rep.stats:
            assert s.predict_calls in (0, len(budgets))

    def test_front_members_meet_budget_and_are_nondominated(self, served):
        budgets = [DeviceBudget(SOURCE, served["budget_s"])]
        rep = SearchEngine(served["service"], budgets, small_config()).run()
        pts = [m.objectives for m in rep.front]
        for m in rep.front:
            assert m.latencies[budgets[0].key] <= budgets[0].budget_s
        for i in range(len(pts)):
            for j in range(len(pts)):
                if i != j:
                    assert not dominates(pts[i], pts[j])

    def test_checkpoint_resume_bit_equivalence(self, served, tmp_path):
        budgets = [DeviceBudget(SOURCE, served["budget_s"])]
        cfg = small_config(generations=6, seed=31)
        straight = SearchEngine(served["service"], budgets, cfg).run()

        path = str(tmp_path / "search.json")
        eng = SearchEngine(served["service"], budgets, cfg)
        for _ in range(3):
            eng.step()
        eng.save(path)
        resumed = SearchEngine.load(path, served["service"]).run()
        assert straight.front_json() == resumed.front_json()
        assert [s.to_json() for s in straight.stats] == \
            [s.to_json() for s in resumed.stats]
        # Saved-state JSON round-trips to an identical checkpoint.
        eng2 = SearchEngine.load(path, served["service"])
        path2 = str(tmp_path / "again.json")
        eng2.save(path2)
        assert json.load(open(path)) == json.load(open(path2))

    def test_report_verify_measures_front_only(self, served):
        budgets = [DeviceBudget(SOURCE, served["budget_s"])]
        rep = SearchEngine(served["service"], budgets, small_config()).run()
        verify_sess = CostModelProfileSession(seed=3)
        out = rep.verify(verify_sess)
        assert out["n_verified"] == len(rep.front) == verify_sess.measured_graphs
        assert np.isfinite(out["mape"])
        # Predicted-vs-measured: the bank was trained on this very cost
        # model, so front predictions track measurements.
        assert out["mape"] < 1.0
        # A device the search never scored has nothing to verify against.
        with pytest.raises(ValueError, match="not among the searched"):
            rep.verify(CostModelProfileSession(seed=3),
                       DeviceSetting("other", "int8", "op_by_op"))


class TestMultiDevice:
    @pytest.fixture(scope="class")
    def two_device(self, served):
        """Register a transfer-calibrated synthetic second device."""
        hub, store = served["hub"], served["store"]
        if hub.get(TARGET, "gbdt") is None:
            device = SyntheticDevice("sim", seed=7, noise=0.1,
                                     base_scale=3.0)
            tsess = ReplayProfileSession(store, device, SOURCE)
            TransferEngine(SOURCE, TARGET, family="gbdt", seed=0).adapt(
                store, hub, tsess, 24)
        svc = LatencyService(hub, default_setting=SOURCE, predictor="gbdt")
        return {**served, "service": svc}

    def test_scorer_filters_on_every_device(self, two_device):
        svc = two_device["service"]
        graphs = [sample_architecture(s, SPACE) for s in range(300, 316)]
        loose = LatencyScorer(svc, [DeviceBudget(SOURCE, 1e9),
                                    DeviceBudget(TARGET, 1e9)])
        lats = loose.score(graphs)
        assert set(lats) == {loose.budgets[0].key, loose.budgets[1].key}
        assert loose.feasible_mask(lats).all()
        # Tighten ONLY the second device to its median: some candidates
        # that pass device 1 must now fail the joint constraint.
        t_med = float(np.median(lats[DeviceBudget(TARGET, 0).key]))
        tight = LatencyScorer(svc, [DeviceBudget(SOURCE, 1e9),
                                    DeviceBudget(TARGET, t_med)])
        mask = tight.feasible_mask(lats)
        assert 0 < mask.sum() < len(graphs)
        viol = tight.violation(lats)
        assert (viol[~mask] > 0).all() and (viol[mask] == 0).all()

    def test_search_respects_both_budgets(self, two_device):
        svc = two_device["service"]
        # Budget the target device near the typical scaled latency so
        # the constraint actually bites.
        probe = [sample_architecture(s, SPACE) for s in range(400, 408)]
        t_lat = [r.e2e_s for r in svc.predict_batch(probe, TARGET)]
        budgets = [DeviceBudget(SOURCE, two_device["budget_s"]),
                   DeviceBudget(TARGET, float(np.median(t_lat)))]
        rep = SearchEngine(svc, budgets, small_config(seed=13)).run()
        assert len(rep.front) > 0
        for m in rep.front:
            for b in budgets:
                assert m.latencies[b.key] <= b.budget_s
        # The joint constraint filtered someone: the run saw infeasible
        # candidates (else the second budget was vacuous).
        assert any(s.feasible_new < s.new_scored for s in rep.stats)
        # Objectives span both devices + quality.
        assert len(rep.front[0].objectives) == 3


# ---------------------------------------------------------------------------
# Service-side satellites: auto backend recording + multi-setting queries
# ---------------------------------------------------------------------------

class TestServiceBackend:
    def test_small_batch_runs_numpy_and_is_recorded(self, served):
        svc = LatencyService(served["hub"], default_setting=SOURCE,
                             predictor="gbdt")
        assert svc.inference_backend == "auto"
        svc.predict_batch([sample_architecture(900, SPACE)])
        st = svc.stats()
        assert st["predict_batch_calls"] == 1
        assert st["backend_runs"].get("numpy", 0) > 0
        assert st["backend_runs"].get("jax", 0) == 0

    def test_auto_crosses_to_jax_at_threshold(self, served, monkeypatch):
        import repro.core.predictors.flat as flat_mod
        monkeypatch.setattr(flat_mod, "AUTO_JAX_MIN_SLOTS", 1)
        svc = LatencyService(served["hub"], default_setting=SOURCE,
                             predictor="gbdt")
        reports = svc.predict_batch(
            [sample_architecture(s, SPACE) for s in (901, 902)])
        st = svc.stats()
        assert st["backend_runs"].get("jax", 0) > 0
        assert all(np.isfinite(r.e2e_s) and r.e2e_s > 0 for r in reports)

    def test_forced_numpy_backend(self, served):
        svc = LatencyService(served["hub"], default_setting=SOURCE,
                             predictor="gbdt", inference_backend="numpy")
        svc.predict_batch([sample_architecture(903, SPACE)])
        assert "jax" not in svc.stats()["backend_runs"]


class TestPredictMulti:
    def test_one_call_per_setting_and_matching_reports(self, served):
        hub = served["hub"]
        if hub.get(TARGET, "gbdt") is None:
            device = SyntheticDevice("sim", seed=7, noise=0.1, base_scale=3.0)
            tsess = ReplayProfileSession(served["store"], device, SOURCE)
            TransferEngine(SOURCE, TARGET, family="gbdt", seed=0).adapt(
                served["store"], hub, tsess, 24)
        svc = LatencyService(hub, default_setting=SOURCE, predictor="gbdt")
        graphs = [sample_architecture(s, SPACE) for s in (910, 911, 912)]
        multi = svc.predict_multi(graphs, [SOURCE, TARGET])
        assert svc.stats()["predict_batch_calls"] == 2
        assert set(multi) == {"float32/op_by_op", "sim:float32/op_by_op"}
        single = svc.predict_batch(graphs, TARGET)
        assert [r.e2e_s for r in multi["sim:float32/op_by_op"]] == \
            [r.e2e_s for r in single]

# ---------------------------------------------------------------------------
# Genotype families: elastic knobs, random-wired DAGs, golden fingerprints
# ---------------------------------------------------------------------------

GOLDEN = os.path.join(os.path.dirname(__file__), "golden",
                      "randwired_fingerprints.json")


@pytest.fixture(scope="module")
def served_rw():
    """Service whose bank has seen random-wired op types (elementwise
    joins, concat/resize from encoder-decoder skeletons) — chains alone
    don't cover them."""
    golden = json.load(open(GOLDEN))
    rwc = RandomWiredConfig(**golden["rw_config"])
    store = ProfileStore()
    session = CostModelProfileSession(store=store, seed=3)
    graphs = synthetic_graphs(8, resolution=16)
    graphs += [decode_genotype(sample_random_wired(s, rwc), SPACE)
               for s in range(4)]
    for g in graphs:
        session.profile_graph(g, SOURCE)
    hub = PredictorHub()
    hub.train(store, SOURCE, "gbdt", hparams={"n_stages": 20}, min_samples=3)
    svc = LatencyService(hub, default_setting=SOURCE, predictor="gbdt")
    e2e = [store.get_arch(SOURCE, g.fingerprint()).e2e_s for g in graphs]
    return {"service": svc, "budget_s": float(np.median(e2e)), "rwc": rwc}


class TestGoldenFingerprints:
    """Differential decode: 200 seeded genotypes pinned by golden file.

    Any drift in the samplers, serialization, or decoders shows up as a
    digest/fingerprint mismatch against `tests/golden/`."""

    @pytest.fixture(scope="class")
    def golden(self):
        return json.load(open(GOLDEN))

    def test_random_wired_pinned(self, golden):
        rwc = RandomWiredConfig(**golden["rw_config"])
        space = NASSpaceConfig(**golden["space"])
        assert len(golden["random_wired"]) == 120
        for seed, (digest, fp) in sorted(golden["random_wired"].items(),
                                         key=lambda kv: int(kv[0])):
            gt = sample_random_wired(int(seed), rwc)
            assert gt.digest() == digest, f"rw seed {seed}: digest drift"
            clone = genotype_from_json(json.loads(json.dumps(gt.to_json())))
            assert clone == gt
            g = decode_genotype(clone, space)
            assert g.fingerprint() == fp, f"rw seed {seed}: decode drift"

    def test_elastic_pinned(self, golden):
        space = NASSpaceConfig(**golden["space"])
        assert len(golden["elastic"]) == 80
        for seed, (digest, fp) in sorted(golden["elastic"].items(),
                                         key=lambda kv: int(kv[0])):
            gt = sample_elastic_genotype(int(seed), space)
            assert gt.digest() == digest, f"elastic seed {seed}: digest drift"
            clone = genotype_from_json(json.loads(json.dumps(gt.to_json())))
            assert clone == gt and clone.family == "elastic"
            g = decode_genotype(clone, space)
            assert g.fingerprint() == fp, f"elastic seed {seed}: decode drift"

    def test_some_golden_graph_has_wide_fanout(self, golden):
        rwc = RandomWiredConfig(**golden["rw_config"])
        space = NASSpaceConfig(**golden["space"])
        best = 0
        for seed in range(20):
            g = decode_genotype(sample_random_wired(seed, rwc), space)
            fanout = {}
            for n in g.nodes:
                for t in n.inputs:
                    fanout[t] = fanout.get(t, 0) + 1
            best = max(best, max(fanout.values()))
        assert best >= 3


class TestElasticFamily:
    def test_shrink_grow_deterministic_and_single_knob(self):
        gt = sample_elastic_genotype(5, SPACE)
        assert gt.family == "elastic"
        for op in (shrink, grow, mutate_elastic):
            a = op(gt, np.random.default_rng(3), SPACE)
            b = op(gt, np.random.default_rng(3), SPACE)
            assert a == b and a.family == "elastic"
        small = shrink(gt, np.random.default_rng(3), SPACE)
        # Exactly one block differs, and within it at most one knob
        # moved (repair may not touch the others: same macro-skeleton).
        diff = [i for i, (x, y) in enumerate(zip(gt.blocks, small.blocks))
                if x != y]
        assert len(diff) <= 1
        if diff:
            x, y = gt.blocks[diff[0]], small.blocks[diff[0]]
            changed = sum(getattr(x, k) != getattr(y, k)
                          for k in ("kernel", "depth", "expansion", "out_c"))
            assert changed == 1

    def test_supernet_quality_needs_genotype(self):
        q = make_quality("supernet")
        assert getattr(q, "needs_genotype", False)
        gt = sample_elastic_genotype(2, SPACE)
        assert 0.0 < q(gt) <= 1.0
        with pytest.raises(TypeError, match="genotypes"):
            q(decode_genotype(gt, SPACE))

    def test_elastic_mutate_dispatch(self):
        gt = sample_elastic_genotype(9, SPACE)
        out = mutate(gt, np.random.default_rng(1), SPACE)
        assert out.family == "elastic"
        assert repair(out, SPACE) == out

    def test_elastic_search_deterministic_with_resume(self, served_rw,
                                                      tmp_path):
        budgets = [DeviceBudget(SOURCE, served_rw["budget_s"] * 4)]
        cfg = small_config(population_size=8, generations=4,
                           children_per_gen=6, seed=17, family="elastic",
                           quality="supernet")
        r1 = SearchEngine(served_rw["service"], budgets, cfg).run()
        r2 = SearchEngine(served_rw["service"], budgets, cfg).run()
        assert r1.front_json() == r2.front_json()
        assert len(r1.front) > 0
        for m in r1.front:
            gt = genotype_from_json(m.genotype)
            assert gt.family == "elastic"
        path = str(tmp_path / "elastic.json")
        eng = SearchEngine(served_rw["service"], budgets, cfg)
        eng.step(); eng.step()
        eng.save(path)
        resumed = SearchEngine.load(path, served_rw["service"]).run()
        assert resumed.front_json() == r1.front_json()


class TestRandomWiredFamily:
    def test_mutate_crossover_dispatch_and_family_guard(self):
        rng = np.random.default_rng(0)
        a = sample_random_wired(1, RandomWiredConfig(nodes_per_stage=5))
        b = sample_random_wired(2, RandomWiredConfig(nodes_per_stage=5))
        m = mutate(a, rng, SPACE)
        assert isinstance(m, RandomWiredGenotype)
        assert repair(m, SPACE) == m
        c = crossover(a, b, np.random.default_rng(4), SPACE)
        assert isinstance(c, RandomWiredGenotype)
        assert crossover(a, b, np.random.default_rng(4), SPACE) == c
        with pytest.raises(ValueError, match="families"):
            crossover(a, sample_genotype(0, SPACE), rng, SPACE)

    def test_random_wired_search_deterministic_with_resume(self, served_rw,
                                                           tmp_path):
        budgets = [DeviceBudget(SOURCE, served_rw["budget_s"] * 20)]
        cfg = small_config(population_size=8, generations=4,
                           children_per_gen=6, seed=29,
                           family="random_wired",
                           rw=served_rw["rwc"].to_json())
        r1 = SearchEngine(served_rw["service"], budgets, cfg).run()
        r2 = SearchEngine(served_rw["service"], budgets, cfg).run()
        assert r1.front_json() == r2.front_json()
        assert len(r1.front) > 0
        for m in r1.front:
            gt = genotype_from_json(m.genotype)
            assert isinstance(gt, RandomWiredGenotype)
        path = str(tmp_path / "rw.json")
        eng = SearchEngine(served_rw["service"], budgets, cfg)
        eng.step(); eng.step()
        eng.save(path)
        resumed = SearchEngine.load(path, served_rw["service"]).run()
        assert resumed.front_json() == r1.front_json()
        # Config JSON for a random-wired run round-trips its rw knobs.
        saved = json.load(open(path))
        assert saved["config"]["family"] == "random_wired"
