"""Search cells: a NAS loop scoring whole generations.

The window drives `SearchEngine.step` on the program's
`LatencyService` (tier ``auto``).  A cycle is a fresh engine that runs
``cycle_generations`` generations from the traffic's fixed engine seed
on a cleared report cache; set-up runs one cycle, which compiles every
program the cycle's per-type flushes need, and the window repeats
cycles until ``--seconds`` have passed, ending with the first
generation that ends after that.  Each cycle therefore scores the same
candidates with the same shapes, so nothing compiles in the window.
``--seed`` reorders the candidates of every scoring call, which the
service's contract makes immaterial to the result.
"""
from __future__ import annotations

import time
from typing import Any, Dict, List, Optional, Sequence, Tuple

import numpy as np

from chipbench import bank as bankmod
from chipbench.compare import Answers
from chipbench.record import Run


class RecordingService:
    """The engine's view of the service: each scoring call's graphs go
    to the real service in an order drawn from the seed, come back in
    the engine's order, and are recorded while ``recording`` is set."""

    def __init__(self, service: Any, seed: int):
        self.service = service
        self.rng = np.random.default_rng(seed)
        self.recording = False
        self.answers = Answers()
        self.calls: List[Tuple[float, float]] = []

    def predict_multi(self, graphs: Sequence[Any], settings: Sequence[Any],
                      predictor: Optional[str] = None) -> Dict[str, List[Any]]:
        perm = self.rng.permutation(len(graphs))
        t = time.perf_counter()
        out = self.service.predict_multi([graphs[i] for i in perm],
                                         settings, predictor)
        t1 = time.perf_counter()
        inv = np.argsort(perm)
        res = {k: [v[i] for i in inv] for k, v in out.items()}
        if self.recording:
            self.calls.append((t, t1))
            for g, r in zip(graphs, next(iter(res.values()))):
                self.answers.add(g, r)
        return res


def drive(cfg: Dict[str, Any], spec: Dict[str, Any], *, hub: Any, obs: Any,
          seed: int, seconds: float, on_window: Any) -> Tuple[Run, Answers]:
    """Set-up cycle, then the window; returns the run and the window's
    reports.  ``on_window(start)`` is called with True
    just before the window opens and False just after it closes (the
    trace hooks)."""
    from repro.pipeline import LatencyService
    from repro.search.evolution import SearchConfig, SearchEngine
    from repro.search.objectives import DeviceBudget

    st = bankmod.setting(cfg)
    service = LatencyService(hub, default_setting=st,
                             predictor=cfg["bank"]["predictor"], obs=obs)
    rec = RecordingService(service, seed)
    scfg = SearchConfig(
        population_size=spec["population"],
        generations=1 << 30,
        children_per_gen=spec["children"],
        tournament_size=spec["tournament"],
        crossover_prob=spec["crossover"],
        seed=spec["engine_seed"],
        quality=spec["quality"],
        resolution=cfg["resolution"],
        channel_scale=cfg.get("channel_scale", 1.0),
        family=cfg["family"],
        rw=cfg.get("rw"))
    budgets = [DeviceBudget(st, cfg["budget_s"])]
    k = spec["cycle_generations"]
    annotations: List[Tuple[str, float, float]] = []

    def cycle():
        service.clear_cache()
        eng = SearchEngine(rec, budgets, scfg, predictor=cfg["bank"]["predictor"])
        for _ in range(k):
            t = time.perf_counter()
            stats = eng.step()
            annotations.append(("search.generation", t, time.perf_counter()))
            yield stats

    for _ in cycle():                       # set-up: compiles every shape
        pass
    annotations.clear()
    cands = 0
    rec.recording = True
    on_window(True)
    t0 = time.perf_counter()
    done = False
    while not done:
        for stats in cycle():
            cands += stats.new_scored
            if time.perf_counter() - t0 >= seconds:
                done = True
                break
    t1 = time.perf_counter()
    on_window(False)
    rec.recording = False
    run = Run(kind="search", cell="", chips=0, setup_s=0.0, t0=t0, t1=t1)
    run.cands = cands
    run.attempted = len(rec.answers)
    run.annotations = annotations + [("search.score", a, b)
                                     for a, b in rec.calls]
    gens = sorted(b - a for _, a, b in annotations)
    run.notes["generation_s"] = {"n": len(gens), "min": gens[0],
                                 "median": gens[len(gens) // 2],
                                 "max": gens[-1]}
    return run, rec.answers
