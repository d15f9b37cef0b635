"""Graphs of an MLA + shared-expert MoE deployment (DeepSeek-V2): one
chip's step of a chunked-prefill server, lowered by the program's
`repro.core.lm_lowering.lower_step` at the configuration's published
widths.

A draw is a scheduler state (running decodes with their caches, waiting
prompts with the part already prefilled) and an expert share (which of
the experts this chip holds are popular in each MoE layer), then one of
the candidate steps a scheduler would weigh in that state.  The
distributions are the configuration's ``train_mix`` for the bank and
the digest, and the traffic file's for a window.
"""
from __future__ import annotations

from typing import Any, Dict, List

import numpy as np

from repro.configs.base import ArchConfig
from repro.configs.deepseek_v2_lite import from_hf_config
from repro.core.ir import OpGraph
from repro.core.lm_lowering import ExpertShare, lower_step
from repro.serving.planner import (Prompt, SchedulerState, candidates,
                                   plan_of)


def arch(cfg: Dict[str, Any]) -> ArchConfig:
    return from_hf_config(cfg, name=cfg["name"])


def _log_uniform(rng: np.random.Generator, lo: int, hi: int) -> int:
    return int(round(float(np.exp(rng.uniform(np.log(lo), np.log(hi))))))


def inclusion(p: np.ndarray, k: int) -> np.ndarray:
    """Chance that each expert is among a token's top ``k`` when the
    scores favour them as ``p`` does: ``min(1, c * p)`` with ``c`` set
    so that the chances sum to ``k``."""
    q = np.zeros_like(p)
    capped = np.zeros(len(p), dtype=bool)
    for _ in range(len(p)):
        c = (k - capped.sum()) / p[~capped].sum()
        q = np.where(capped, 1.0, c * p)
        over = (q > 1.0) & ~capped
        if not over.any():
            return q
        capped |= over
    return q


def share(cfg: Dict[str, Any], rng: np.random.Generator,
          zipf: float) -> ExpertShare:
    """Per MoE layer, expert popularity Zipf(``zipf``) over all routed
    experts in a drawn order; this chip holds experts 0 .. held - 1."""
    E, k = cfg["n_routed_experts"], cfg["num_experts_per_tok"]
    layers = cfg["num_hidden_layers"] - cfg["first_k_dense_replace"]
    base = 1.0 / np.arange(1, E + 1) ** zipf
    pop = []
    for _ in range(layers):
        p = base[rng.permutation(E)]
        q = inclusion(p / p.sum(), k)
        pop.append(tuple(float(v) for v in q[:cfg["experts_held"]]))
    return ExpertShare(held=cfg["experts_held"],
                       group=cfg["expert_parallel"], popularity=tuple(pop))


def state(mix: Dict[str, Any], rng: np.random.Generator) -> SchedulerState:
    """Running decodes (caches log-uniform, their total capped) and
    waiting prompts (lengths log-uniform, each partly prefilled)."""
    lo, hi = mix["decodes"]
    decodes: List[int] = []
    for _ in range(int(rng.integers(lo, hi + 1))):
        c = _log_uniform(rng, *mix["cache_tokens"])
        if sum(decodes) + c > mix["cache_capacity"]:
            break
        decodes.append(c)
    waiting = []
    for _ in range(mix["waiting"]):
        n = _log_uniform(rng, *mix["prompt_tokens"])
        waiting.append(Prompt(length=n, done=int(rng.integers(0, n))))
    return SchedulerState(decodes=tuple(decodes), waiting=tuple(waiting))


def sample_graphs(cfg: Dict[str, Any], rng: np.random.Generator,
                  n: int) -> List[OpGraph]:
    """``n`` steps, each a candidate drawn from a fresh state and share
    of the configuration's ``train_mix``."""
    mix = cfg["train_mix"]
    a = arch(cfg)
    out = []
    for _ in range(n):
        sh = share(cfg, rng, mix["zipf"])
        st = state(mix, rng)
        cands = candidates(st, mix["chunk_tokens"])
        c = cands[int(rng.integers(len(cands)))]
        out.append(lower_step(a, plan_of(st, c), sh))
    return out


def training_graphs(cfg: Dict[str, Any]) -> List[OpGraph]:
    b = cfg["bank"]
    return sample_graphs(cfg, np.random.default_rng(b["train_seed"]),
                         b["train_graphs"])
