"""A test-only graph source of small LM step graphs, and its drive module.

Each graph is one step of a mixture-of-experts decoder: per layer a
norm, the QKV projection, attention, the output projection, a second
norm, the router's projection and the grouped expert matmul.  Sizes are
drawn from the generator, prefill and decode steps alike.  The tests
register this module as ``chipbench.graphs_lm_toy`` and
``chipbench.drive_lm_toy``, so it plugs in by name as a configuration's
own graph source and traffic kind would.
"""
from __future__ import annotations

import time
from typing import Any, Dict, List, Tuple

import numpy as np

from repro.core.ir import OpGraph

from chipbench import bank as bankmod
from chipbench.compare import Answers
from chipbench.record import Run


def _step(rng: np.random.Generator) -> OpGraph:
    batch = int(rng.choice([1, 2, 4, 8]))
    q_len = int(rng.choice([1, 16, 64, 256]))
    kv_len = q_len + int(rng.choice([0, 128, 512, 2048]))
    d_model = int(rng.choice([256, 512, 1024]))
    heads = int(rng.choice([4, 8, 16]))
    head_dim = int(rng.choice([64, 128]))
    experts = int(rng.choice([8, 16, 64]))
    top_k = int(rng.choice([1, 2, 6]))
    d_ff = int(rng.choice([256, 704, 1408]))
    layers = int(rng.choice([1, 2]))
    tokens = batch * q_len
    g = OpGraph(f"lm_b{batch}_q{q_len}_kv{kv_len}_d{d_model}_h{heads}x"
                f"{head_dim}_e{experts}k{top_k}f{d_ff}_l{layers}")
    x = g.add_input((tokens, d_model))
    for _ in range(layers):
        (h,) = g.add_op("norm", [x], [(tokens, d_model)])
        (qkv,) = g.add_op("matmul", [h], [(tokens, 3 * heads * head_dim)],
                          {"m": tokens, "n": 3 * heads * head_dim,
                           "k": d_model})
        (a,) = g.add_op("attention", [qkv], [(tokens, heads * head_dim)],
                        {"batch": batch, "q_len": q_len, "kv_len": kv_len,
                         "heads": heads, "head_dim": head_dim})
        (o,) = g.add_op("matmul", [a], [(tokens, d_model)],
                        {"m": tokens, "n": d_model, "k": heads * head_dim})
        (h2,) = g.add_op("norm", [o], [(tokens, d_model)])
        (r,) = g.add_op("matmul", [h2], [(tokens, experts)],
                        {"m": tokens, "n": experts, "k": d_model})
        (x,) = g.add_op("moe_gmm", [h2, r], [(tokens, d_model)],
                        {"experts": experts, "top_k": top_k,
                         "tokens": tokens, "d_model": d_model,
                         "d_ff": d_ff})
    g.mark_output(x)
    return g


def sample_graphs(cfg: Dict[str, Any], rng: np.random.Generator,
                  n: int) -> List[OpGraph]:
    return [_step(rng) for _ in range(n)]


def training_graphs(cfg: Dict[str, Any]) -> List[OpGraph]:
    b = cfg["bank"]
    return sample_graphs(cfg, np.random.default_rng(b["train_seed"]),
                         b["train_graphs"])


def drive(cfg: Dict[str, Any], spec: Dict[str, Any], *, hub: Any, obs: Any,
          seed: int, seconds: float, on_window: Any) -> Tuple[Run, Answers]:
    """Score ``spec["graphs"]`` graphs drawn from ``seed`` through the
    program's service: once in set-up, then in the window until
    ``seconds`` have passed."""
    from repro.pipeline import LatencyService

    service = LatencyService(hub, default_setting=bankmod.setting(cfg),
                             predictor=cfg["bank"]["predictor"], obs=obs)
    graphs = sample_graphs(cfg, np.random.default_rng(seed), spec["graphs"])
    service.predict_batch(graphs)
    answers = Answers()
    on_window(True)
    t0 = time.perf_counter()
    while True:
        service.clear_cache()
        for g, r in zip(graphs, service.predict_batch(graphs)):
            answers.add(g, r)
        if time.perf_counter() - t0 >= seconds:
            break
    t1 = time.perf_counter()
    on_window(False)
    run = Run(kind="lm_toy", cell="", chips=0, setup_s=0.0, t0=t0, t1=t1)
    run.attempted = len(answers)
    return run, answers
