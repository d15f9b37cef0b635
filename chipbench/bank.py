"""A configuration's bank, its device setting, and its traffic guard.

The bank plays the part that weights play for a model: it is built at
set-up from the configuration's fixed seed (analytic latencies of the
training graphs its graph source gives, then one GBDT fit with fixed
hyperparameters), so every run of every seed scores against identical
trees.  The graphs come from ``chipbench/graphs_<name>.py`` (see
`chipbench.spec`); set-up stops before the window if one of them holds
an op type that the configuration's reference has no features for.
"""
from __future__ import annotations

import hashlib
import os
from typing import Any, Dict, List, Sequence, Tuple

import numpy as np

from repro.core.ir import OpGraph
from repro.core.profiler import DeviceSetting
from repro.pipeline import PredictorHub, ProfileStore
from repro.transfer import CostModelProfileSession

from chipbench import spec

DIGEST_GRAPHS = 64


def setting(cfg: Dict[str, Any]) -> DeviceSetting:
    s = cfg["setting"]
    return DeviceSetting(s["name"], s["dtype"], s["mode"])


def check_reference(cfg: Dict[str, Any], graphs: Sequence[OpGraph],
                    what: str) -> None:
    """Raise if an op type of ``graphs`` has no reference features."""
    table = spec.reference_features(cfg)
    missing = sorted({n.op_type for g in graphs for n in g.nodes}
                     - set(table))
    if missing:
        raise RuntimeError(
            f"configuration {cfg['name']}: op type(s) {', '.join(missing)} "
            f"of its {what} have no reference features in "
            f"chipbench/reference_{spec.graphs_name(cfg)}.py")


def train_hub(cfg: Dict[str, Any], root: str) -> Tuple[PredictorHub,
                                                       DeviceSetting]:
    """Profile the configuration's training graphs through the analytic
    cost model and fit one bank; the bank is also saved under ``root``
    as the JSON the reference reads."""
    b = cfg["bank"]
    st = setting(cfg)
    graphs = spec.graph_source(cfg).training_graphs(cfg)
    check_reference(cfg, graphs, "training graphs")
    store = ProfileStore()
    session = CostModelProfileSession(store=store, seed=b["profile_seed"])
    session.profile_suite(graphs, st)
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
    """``n`` graphs from the configuration's generator."""
    return spec.graph_source(cfg).sample_graphs(cfg, rng, n)


def _digest_graphs(cfg: Dict[str, Any]) -> List[OpGraph]:
    return sample_graphs(cfg, np.random.default_rng(0), DIGEST_GRAPHS)


def _digest(graphs: Sequence[OpGraph]) -> str:
    blob = "\n".join(g.fingerprint() for g in graphs).encode()
    return hashlib.sha256(blob).hexdigest()


def traffic_digest(cfg: Dict[str, Any]) -> str:
    """sha256 over the fingerprints of the first 64 graphs the
    configuration's generator gives for seed 0."""
    return _digest(_digest_graphs(cfg))


def check_traffic_digest(cfg: Dict[str, Any]) -> None:
    """Raise if the generator changed, or if its graphs hold an op type
    that the reference cannot featurize."""
    graphs = _digest_graphs(cfg)
    got = _digest(graphs)
    if got != cfg["traffic_digest"]:
        raise RuntimeError(
            f"configuration {cfg['name']}: the graph generator changed "
            f"(digest {got}, configuration records "
            f"{cfg['traffic_digest']}); this is a change of the benchmark")
    check_reference(cfg, graphs, "generator's graphs")
