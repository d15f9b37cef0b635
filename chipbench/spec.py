"""Finds a cell's pieces by name: BENCHMARK.json, configuration and
traffic files, peaks, the reader of each metric, and the modules a
cell's data names.

Three kinds of module plug in by name, each a file of its own under
``chipbench/``, so that a new cell or configuration adds files and
edits none:

* ``drive_<kind>.py``: drives a traffic file's ``kind``;
* ``graphs_<name>.py``: the graphs of a configuration whose file says
  ``"graphs": "<name>"`` (``nas`` where it says nothing), with
  ``sample_graphs(cfg, rng, n)`` (the generator) and
  ``training_graphs(cfg)`` (what the bank is profiled on);
* ``reference_<name>.py``: the same configuration's reference op
  features, ``FEATURES`` (op type to feature list, read from a graph's
  JSON wire form), for the op types its graphs bring.
"""
from __future__ import annotations

import importlib
import importlib.util
import json
import os
from typing import Any, Callable, Dict, List

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)
CACHE_DIR = os.path.join(ROOT, ".jax_cache")


def use_checkout_cache() -> str:
    """Point JAX's persistent compilation cache at a fixed directory of
    the checkout, which the program's `enable_compile_cache` then uses.
    Call before importing JAX.  The cache is unbounded: with a size
    limit JAX keeps access-time files beside its entries, and on the
    chip's machines a missing one made every write fail, so nothing was
    ever cached."""
    os.makedirs(CACHE_DIR, exist_ok=True)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = CACHE_DIR
    os.environ["JAX_COMPILATION_CACHE_MAX_SIZE"] = "-1"
    return CACHE_DIR


def _load_json(path: str) -> Any:
    with open(path) as f:
        return json.load(f)


def benchmark() -> Dict[str, Any]:
    return _load_json(os.path.join(ROOT, "BENCHMARK.json"))


def cell(bench: Dict[str, Any], name: str) -> Dict[str, Any]:
    for w in bench["workloads"]:
        if w["name"] == name:
            return w
    raise KeyError(f"no workload {name!r} in BENCHMARK.json; known: "
                   f"{[w['name'] for w in bench['workloads']]}")


def config(bench: Dict[str, Any], name: str) -> Dict[str, Any]:
    for c in bench["configs"]:
        if c["name"] == name:
            return _load_json(os.path.join(ROOT, c["file"]))
    raise KeyError(f"no configuration {name!r} in BENCHMARK.json")


def traffic(name: str) -> Dict[str, Any]:
    return _load_json(os.path.join(BENCH_DIR, "traffic", f"{name}.json"))


def peaks(device_kind: str) -> Dict[str, Any]:
    table = _load_json(os.path.join(BENCH_DIR, "peaks.json"))
    if device_kind not in table:
        raise KeyError(f"device kind {device_kind!r} is not in peaks.json "
                       f"(known: {sorted(table)})")
    return table[device_kind]


def cell_metrics(bench: Dict[str, Any], cell_name: str,
                 section: str) -> List[Dict[str, Any]]:
    """The metrics of ``section`` ("end_to_end" or "per_layer") that
    ``cell_name`` reports: those that list it, and those that list no
    cells but move, or are, an end-to-end metric the cell reports."""
    e2e = [m for m in bench["end_to_end"]
           if cell_name in m.get("workloads", [cell_name])]
    if section == "end_to_end":
        return e2e
    names = {m["name"] for m in e2e}
    return [m for m in bench["per_layer"]
            if cell_name in m.get("workloads", [cell_name])
            and m["moves"] in names]


def plugin(prefix: str, name: str, what: str) -> Any:
    """``chipbench.<prefix>_<name>``; ``what`` says who named it."""
    module = f"chipbench.{prefix}_{name}"
    try:
        return importlib.import_module(module)
    except ModuleNotFoundError as e:
        if e.name != module:
            raise
        raise ValueError(f"unknown {what} {name!r}: no "
                         f"chipbench/{prefix}_{name}.py") from None


def graphs_name(cfg: Dict[str, Any]) -> str:
    return cfg.get("graphs", "nas")


def graph_source(cfg: Dict[str, Any]) -> Any:
    """The module of ``cfg``'s graphs (``graphs_<name>.py``)."""
    return plugin("graphs", graphs_name(cfg),
                  f"graph source of configuration {cfg['name']}")


def reference_features(cfg: Dict[str, Any]) -> Dict[str, Any]:
    """``FEATURES`` of ``cfg``'s reference (``reference_<name>.py``)."""
    return plugin("reference", graphs_name(cfg),
                  f"reference of configuration {cfg['name']}").FEATURES


def reader(metric_name: str) -> Callable[[Any], Any]:
    """``read(run)`` of ``metrics/<metric_name>.py``."""
    path = os.path.join(BENCH_DIR, "metrics", f"{metric_name}.py")
    spec = importlib.util.spec_from_file_location(
        "chipbench_metric_" + metric_name.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read
