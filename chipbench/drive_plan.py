"""Plan cells: a chunked-prefill scheduler choosing each step.

The window drives the program's `StepPlanner.decide` on its
`LatencyService` (tier ``auto``): before each step the scheduler's
candidate plans (the decodes alone, and every waiting prompt at every
chunk size) are lowered to one chip's step graph and scored in one
call, and the largest plan within the step budget is taken.  The
starting state and the expert share are drawn from the traffic's fixed
engine seed.  A cycle runs ``decisions_per_cycle`` decisions from that
state on a cleared report cache; set-up runs one cycle, which compiles
every program the cycle's per-type flushes need, and the window repeats
cycles until ``--seconds`` have passed, ending with the first decision
that ends after that.  Nothing compiles in the window.  ``--seed``
reorders the candidates of every decision, which the planner's
contract makes immaterial to the pick.
"""
from __future__ import annotations

import time
from typing import Any, Dict, List, Tuple

import numpy as np

from chipbench import bank as bankmod
from chipbench import graphs_mla_moe as graphs
from chipbench.compare import Answers
from chipbench.record import Run


def drive(cfg: Dict[str, Any], spec: Dict[str, Any], *, hub: Any, obs: Any,
          seed: int, seconds: float, on_window: Any) -> Tuple[Run, Answers]:
    """Set-up cycle, then the window; returns the run and the window's
    reports.  ``on_window(start)`` is called with True just before the
    window opens and False just after it closes (the trace hooks)."""
    from repro.pipeline import LatencyService
    from repro.serving.planner import StepPlanner, candidates

    service = LatencyService(hub, default_setting=bankmod.setting(cfg),
                             predictor=cfg["bank"]["predictor"], obs=obs)
    mix = spec["mix"]
    engine = np.random.default_rng(spec["engine_seed"])
    share = graphs.share(cfg, engine, mix["zipf"])
    start = graphs.state(mix, engine)
    planner = StepPlanner(service, graphs.arch(cfg), share, cfg["budget_s"],
                          obs=obs)
    order = np.random.default_rng(seed)
    answers = Answers()
    annotations: List[Tuple[str, float, float]] = []
    recording = False

    def cycle():
        service.clear_cache()
        state = start
        for _ in range(spec["decisions_per_cycle"]):
            cands = candidates(state, mix["chunk_tokens"])
            cands = [cands[i] for i in order.permutation(len(cands))]
            t = time.perf_counter()
            d = planner.decide(state, cands)
            annotations.append(("plan.decision", t, time.perf_counter()))
            if recording:
                for g, r in zip(d.graphs, d.reports):
                    answers.add(g, r)
            state = d.state
            yield sum(not r.from_cache for r in d.reports)

    for _ in cycle():                       # set-up: compiles every shape
        pass
    annotations.clear()
    cands = 0
    recording = True
    on_window(True)
    t0 = time.perf_counter()
    done = False
    while not done:
        for fresh in cycle():
            cands += fresh
            if time.perf_counter() - t0 >= seconds:
                done = True
                break
    t1 = time.perf_counter()
    on_window(False)
    run = Run(kind="search", cell="", chips=0, setup_s=0.0, t0=t0, t1=t1)
    run.cands = cands
    run.attempted = len(answers)
    run.annotations = annotations
    took = sorted(b - a for _, a, b in annotations)
    run.notes["decision_s"] = {"n": len(took), "min": took[0],
                               "median": took[len(took) // 2],
                               "max": took[-1]}
    return run, answers
