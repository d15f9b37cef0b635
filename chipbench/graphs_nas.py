"""Graphs of the NAS configurations: the paper's block space and the
random-wired family, decoded by the program's own search encoding, and
a bank profiled on synthetic graphs of the paper's space."""
from __future__ import annotations

from typing import Any, Dict, List

import numpy as np

from repro.core.dataset import synthetic_graphs
from repro.core.ir import OpGraph
from repro.core.nas_space import NASSpaceConfig, RandomWiredConfig
from repro.search import encoding


def space(cfg: Dict[str, Any]) -> NASSpaceConfig:
    return NASSpaceConfig(resolution=cfg["resolution"],
                          channel_scale=cfg.get("channel_scale", 1.0))


def sample_graphs(cfg: Dict[str, Any], rng: np.random.Generator,
                  n: int) -> List[OpGraph]:
    """``n`` graphs from the configuration's generator, the draws that
    seed a search population."""
    sp = space(cfg)
    if cfg["family"] == "random_wired":
        rw = RandomWiredConfig(**cfg["rw"])
        gts = [encoding.random_wired(rng, rw) for _ in range(n)]
    else:
        gts = [encoding.random_genotype(rng, sp) for _ in range(n)]
    return [encoding.decode(gt, sp) for gt in gts]


def training_graphs(cfg: Dict[str, Any]) -> List[OpGraph]:
    b = cfg["bank"]
    return synthetic_graphs(b["train_graphs"],
                            resolution=b["train_resolution"])
