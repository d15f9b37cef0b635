"""Step planner: a chunked-prefill scheduler's choice of the next step,
made on predicted latency (the LM counterpart of `SearchEngine`).

    planner = StepPlanner(service, cfg, share, budget_s=0.05, obs=obs)
    state = SchedulerState(decodes=(40_000, 9_000),
                           waiting=(Prompt(length=65_536, done=8_192),))
    decision = planner.decide(state, candidates(state, (512, 2048)))
    state = decision.state

Before each step the scheduler lists candidate plans (run the decodes
alone, or with a chunk of one waiting prompt), lowers each to the op
graph of one chip's step (`repro.core.lm_lowering`), scores them all in
one `LatencyService.predict_batch` call, and takes the largest one
(most tokens) whose predicted latency is within the step budget; when
none is, the smallest.  Ties go to the lower predicted latency, then
the lower prompt index, so the pick does not depend on the order of
the candidates.
"""
from __future__ import annotations

from dataclasses import dataclass, replace
from typing import Any, List, Sequence, Tuple

from repro.configs.base import ArchConfig
from repro.core.ir import OpGraph
from repro.core.lm_lowering import ExpertShare, StepPlan, lower_step
from repro.obs import Observability


@dataclass(frozen=True)
class Prompt:
    """A waiting prompt: ``done`` of its ``length`` tokens prefilled."""

    length: int
    done: int = 0


@dataclass(frozen=True)
class SchedulerState:
    decodes: Tuple[int, ...] = ()        # cache length of each running decode
    waiting: Tuple[Prompt, ...] = ()


@dataclass(frozen=True, order=True)
class Candidate:
    """Run the decodes, with ``chunk`` tokens of waiting prompt
    ``prompt`` (-1: the decodes alone)."""

    prompt: int = -1
    chunk: int = 0


def candidates(state: SchedulerState,
               chunk_sizes: Sequence[int]) -> List[Candidate]:
    """The decodes alone, then each waiting prompt at each chunk size,
    clipped to what is left of it."""
    out = [Candidate()]
    for i, p in enumerate(state.waiting):
        left = p.length - p.done
        out += [Candidate(i, min(c, left)) for c in chunk_sizes if left > 0]
    return out


def plan_of(state: SchedulerState, cand: Candidate) -> StepPlan:
    if cand.prompt < 0:
        return StepPlan(decodes=state.decodes)
    p = state.waiting[cand.prompt]
    return StepPlan(decodes=state.decodes, chunk=cand.chunk, prefix=p.done,
                    emits=p.done + cand.chunk == p.length)


def advance(state: SchedulerState, cand: Candidate) -> SchedulerState:
    """The state after running ``cand``: every decode's cache grows by
    one; the chunked prompt's prefix grows, and a prompt whose last
    chunk ran joins the decodes."""
    decodes = tuple(c + 1 for c in state.decodes)
    waiting = list(state.waiting)
    if cand.prompt >= 0:
        p = waiting[cand.prompt]
        done = p.done + cand.chunk
        if done >= p.length:
            del waiting[cand.prompt]
            decodes += (p.length,)
        else:
            waiting[cand.prompt] = replace(p, done=done)
    return SchedulerState(decodes=decodes, waiting=tuple(waiting))


@dataclass(frozen=True)
class Decision:
    candidate: Candidate
    plan: StepPlan
    predicted_s: float
    state: SchedulerState           # after the step
    graphs: Tuple[OpGraph, ...]     # every candidate's, in the given order
    reports: Tuple[Any, ...]        # their `PredictionReport`s


class StepPlanner:
    """Picks each step's plan for one chip of a deployment (``cfg``,
    expert ``share``) under ``budget_s`` of predicted step latency.

    ``obs`` receives the spans ``planner.decide`` (attrs ``candidates``,
    ``picked``: the tokens of the plan taken), inside it
    ``planner.lower`` (attrs ``graphs``, ``ops``) and ``planner.score``
    (the service's spans nest under it), and the counters
    ``lm.graphs_lowered`` and ``lm.ops_lowered``.
    """

    def __init__(self, service: Any, cfg: ArchConfig, share: ExpertShare,
                 budget_s: float, *, obs: Observability):
        self.service = service
        self.cfg = cfg
        self.share = share
        self.budget_s = float(budget_s)
        self.obs = obs

    def lower(self, plans: Sequence[StepPlan]) -> List[OpGraph]:
        with self.obs.tracer.span("planner.lower") as span:
            graphs = [lower_step(self.cfg, p, self.share) for p in plans]
            ops = sum(len(g.nodes) for g in graphs)
            span.set_attr("graphs", len(graphs))
            span.set_attr("ops", ops)
        self.obs.registry.inc("lm.graphs_lowered", len(graphs))
        self.obs.registry.inc("lm.ops_lowered", ops)
        return graphs

    def decide(self, state: SchedulerState,
               cands: Sequence[Candidate]) -> Decision:
        if not cands:
            raise ValueError("no candidate plans")
        tracer = self.obs.tracer
        with tracer.span("planner.decide",
                         attrs={"candidates": len(cands)}) as span:
            plans = [plan_of(state, c) for c in cands]
            graphs = self.lower(plans)
            with tracer.span("planner.score"):
                reports = self.service.predict_batch(graphs)
            fits = [i for i, r in enumerate(reports)
                    if r.e2e_s <= self.budget_s]

            def key(i):
                return (plans[i].tokens, -reports[i].e2e_s,
                        -cands[i].prompt, -cands[i].chunk)

            if fits:
                k = max(fits, key=key)
            else:
                k = min(range(len(cands)), key=lambda i: (
                    plans[i].tokens, reports[i].e2e_s, cands[i]))
            span.set_attr("picked", plans[k].tokens)
        return Decision(candidate=cands[k], plan=plans[k],
                        predicted_s=reports[k].e2e_s,
                        state=advance(state, cands[k]),
                        graphs=tuple(graphs), reports=tuple(reports))
