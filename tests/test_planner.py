"""The step planner (`repro.serving.planner`): the largest candidate
within budget, whatever the order of the candidates; the smallest when
none fits; how a step moves the scheduler's state; and the spans and
counters a decision records, with the service's spans under its
scoring span."""
import numpy as np
import pytest

from repro.configs import get_arch
from repro.core.features import featurize
from repro.core.lm_lowering import ExpertShare
from repro.core.profiler import DeviceSetting
from repro.obs import Observability
from repro.pipeline import PredictorHub, ProfileStore
from repro.serving.planner import (Candidate, Prompt, SchedulerState,
                                   StepPlanner, advance, candidates, plan_of)
from repro.transfer import CostModelProfileSession

CFG = get_arch("deepseek-v2-lite").reduced()
SHARE = ExpertShare(held=2, group=4, popularity=tuple(
    (0.3, 0.1) for _ in range(CFG.num_layers - CFG.first_k_dense)))
STATE = SchedulerState(decodes=(40, 7), waiting=(
    Prompt(length=100, done=30), Prompt(length=12, done=4),
    Prompt(length=64, done=0)))
CHUNKS = (4, 8, 16, 32)


class FlopsService:
    """Predicts a graph's FLOPs in GFLOP as its latency in seconds."""

    class Report:
        def __init__(self, e2e_s):
            self.e2e_s, self.from_cache = e2e_s, False

    def __init__(self):
        self.calls = 0

    def predict_batch(self, graphs, setting=None):
        self.calls += 1
        out = []
        for g in graphs:
            flops = 0.0
            for n in g.nodes:
                names, vals = featurize(g, n)
                flops += sum(v for k, v in zip(names, vals) if k == "flops")
            out.append(self.Report(flops * 1e-9))
        return out


def _planner(budget_s, service=None, obs=None):
    return StepPlanner(service or FlopsService(), CFG, SHARE, budget_s,
                       obs=obs or Observability.quiet())


def _expected(budget_s):
    cands = candidates(STATE, CHUNKS)
    svc = FlopsService()
    lat = [r.e2e_s for r in svc.predict_batch(
        [_planner(1.0).lower([plan_of(STATE, c)])[0] for c in cands])]
    return cands, lat


def test_candidates_clip_chunks_to_what_is_left():
    cands = candidates(STATE, CHUNKS)
    assert cands[0] == Candidate()
    assert len(cands) == 1 + 3 * len(CHUNKS)
    assert {c.chunk for c in cands if c.prompt == 1} == {4, 8}
    plan = plan_of(STATE, Candidate(1, 8))
    assert (plan.chunk, plan.prefix, plan.emits) == (8, 4, True)
    assert plan.tokens == 10 and plan.emitting == 3


def test_decide_takes_the_largest_plan_within_budget():
    cands, lat = _expected(None)
    budget = float(np.median(lat))
    fits = [i for i, v in enumerate(lat) if v <= budget]
    most = max(plan_of(STATE, cands[i]).tokens for i in fits)
    assert most < max(plan_of(STATE, c).tokens for c in cands)
    d = _planner(budget).decide(STATE, cands)
    assert d.plan.tokens == most and d.predicted_s <= budget
    assert len(d.graphs) == len(d.reports) == len(cands)


def test_decide_is_stable_under_a_permutation_of_the_candidates():
    cands, lat = _expected(None)
    budget = float(np.median(lat))
    svc = FlopsService()
    picks = set()
    for seed in range(5):
        order = np.random.default_rng(seed).permutation(len(cands))
        d = _planner(budget, svc).decide(STATE, [cands[i] for i in order])
        picks.add(d.candidate)
    assert len(picks) == 1 and svc.calls == 5


def test_decide_takes_the_smallest_plan_when_none_fits():
    d = _planner(0.0).decide(STATE, candidates(STATE, CHUNKS))
    assert d.candidate == Candidate() and d.plan.tokens == 2


def test_advance_grows_caches_and_prefixes():
    s = advance(STATE, Candidate(0, 16))
    assert s.decodes == (41, 8)
    assert s.waiting[0] == Prompt(length=100, done=46)
    s = advance(STATE, Candidate(1, 8))              # the prompt's last chunk
    assert s.decodes == (41, 8, 12)
    assert [p.length for p in s.waiting] == [100, 64]


@pytest.fixture(scope="module")
def service_and_obs(tmp_path_factory):
    from repro.pipeline import LatencyService

    st = DeviceSetting("cost_model_bf16", "bfloat16", "op_by_op")
    planner = _planner(1.0)
    graphs = planner.lower([plan_of(STATE, c)
                            for c in candidates(STATE, CHUNKS)])
    store = ProfileStore()
    CostModelProfileSession(store=store, seed=3).profile_suite(graphs, st)
    hub = PredictorHub(str(tmp_path_factory.mktemp("hub")))
    hub.train(store, st, "gbdt", hparams={"n_stages": 10, "max_depth": 3})
    obs = Observability(tracing=True)
    return LatencyService(hub, default_setting=st, obs=obs), obs


def test_a_decision_records_its_spans_and_counters(service_and_obs):
    service, obs = service_and_obs
    cands = candidates(STATE, CHUNKS)
    d = _planner(1.0, service, obs).decide(STATE, cands)
    spans = {s["name"]: s for s in obs.tracer.export()}
    dec, low, score = (spans["planner.decide"], spans["planner.lower"],
                       spans["planner.score"])
    assert dec["attrs"] == {"candidates": len(cands),
                            "picked": d.plan.tokens}
    ops = sum(len(g.nodes) for g in d.graphs)
    assert low["attrs"] == {"graphs": len(cands), "ops": ops}
    assert low["parent"] == dec["sid"] and score["parent"] == dec["sid"]
    for name in ("service.fingerprint", "service.predict_batch"):
        assert spans[name]["parent"] == score["sid"]
    assert score["start"] >= low["end"]
    assert obs.registry.total("lm.graphs_lowered") == len(cands)
    assert obs.registry.total("lm.ops_lowered") == ops
