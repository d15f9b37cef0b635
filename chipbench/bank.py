"""A configuration's bank, its device setting, and its traffic guard.

The bank plays the part that weights play for a model: it is built at
set-up from the configuration's fixed seed (analytic latencies of
synthetic graphs, then one GBDT fit with fixed hyperparameters), so
every run of every seed scores against identical trees.
"""
from __future__ import annotations

import hashlib
import os
from typing import Any, Dict, List, Tuple

import numpy as np

from repro.core.dataset import synthetic_graphs
from repro.core.ir import OpGraph
from repro.core.nas_space import NASSpaceConfig, RandomWiredConfig
from repro.core.profiler import DeviceSetting
from repro.pipeline import PredictorHub, ProfileStore
from repro.search import encoding
from repro.transfer import CostModelProfileSession

DIGEST_GRAPHS = 64


def setting(cfg: Dict[str, Any]) -> DeviceSetting:
    s = cfg["setting"]
    return DeviceSetting(s["name"], s["dtype"], s["mode"])


def space(cfg: Dict[str, Any]) -> NASSpaceConfig:
    return NASSpaceConfig(resolution=cfg["resolution"],
                          channel_scale=cfg.get("channel_scale", 1.0))


def train_hub(cfg: Dict[str, Any], root: str) -> Tuple[PredictorHub,
                                                       DeviceSetting]:
    """Profile the configuration's training graphs through the analytic
    cost model and fit one bank; the bank is also saved under ``root``
    as the JSON the reference reads."""
    b = cfg["bank"]
    st = setting(cfg)
    store = ProfileStore()
    session = CostModelProfileSession(store=store, seed=b["profile_seed"])
    session.profile_suite(
        synthetic_graphs(b["train_graphs"], resolution=b["train_resolution"]),
        st)
    hub = PredictorHub(root)
    hub.train(store, st, b["predictor"], hparams=dict(b["hparams"]),
              seed=b["fit_seed"], overhead_model=b["overhead_model"])
    return hub, st


def bank_file(root: str) -> str:
    files = [f for f in os.listdir(root)
             if f.startswith("bank__") and f.endswith(".json")]
    if len(files) != 1:
        raise RuntimeError(f"expected one saved bank in {root}, got {files}")
    return os.path.join(root, files[0])


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


def traffic_digest(cfg: Dict[str, Any]) -> str:
    """sha256 over the fingerprints of the first 64 graphs the
    configuration's generator gives for seed 0."""
    graphs = sample_graphs(cfg, np.random.default_rng(0), DIGEST_GRAPHS)
    blob = "\n".join(g.fingerprint() for g in graphs).encode()
    return hashlib.sha256(blob).hexdigest()


def check_traffic_digest(cfg: Dict[str, Any]) -> None:
    got = traffic_digest(cfg)
    if got != cfg["traffic_digest"]:
        raise RuntimeError(
            f"configuration {cfg['name']}: the graph generator changed "
            f"(digest {got}, configuration records "
            f"{cfg['traffic_digest']}); this is a change of the benchmark")
