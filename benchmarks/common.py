"""Shared benchmark utilities: dataset loading, CSV/JSON emission."""
from __future__ import annotations

import csv
import io
import json
import os
import sys
from typing import Any, Dict, List, Optional, Sequence

from repro.core.dataset import LatencyDataset
from benchmarks.build_datasets import DATA_DIR, dataset_path

REPORT_DIR = os.path.join(os.path.dirname(__file__), "..", "reports")
# Machine-readable bench output; each section names the platform it ran on.
BENCH_JSON = os.path.join(REPORT_DIR, "bench_predict.json")


def load_dataset(kind: str, setting: str) -> Optional[LatencyDataset]:
    path = dataset_path(kind, setting)
    if not os.path.exists(path):
        return None
    return LatencyDataset.load(path)


def require_dataset(kind: str, setting: str) -> LatencyDataset:
    ds = load_dataset(kind, setting)
    if ds is None:
        raise FileNotFoundError(
            f"dataset {kind}/{setting} missing — run "
            f"`PYTHONPATH=src python -m benchmarks.build_datasets` first")
    return ds


def emit_csv(name: str, rows: Sequence[Dict[str, Any]],
             fieldnames: Optional[List[str]] = None) -> None:
    """Print ``name,us_per_call,derived`` style CSV + save under reports/."""
    if not rows:
        print(f"# {name}: no rows")
        return
    fieldnames = fieldnames or list(rows[0].keys())
    buf = io.StringIO()
    w = csv.DictWriter(buf, fieldnames=fieldnames, extrasaction="ignore")
    w.writeheader()
    for r in rows:
        w.writerow(r)
    text = buf.getvalue()
    print(f"# ===== {name} =====")
    print(text)
    os.makedirs(REPORT_DIR, exist_ok=True)
    with open(os.path.join(REPORT_DIR, f"{name}.csv"), "w") as f:
        f.write(text)


def emit_bench_json(section: str, payload: Dict[str, Any]) -> None:
    """Merge ``payload`` under ``section`` into reports/bench_predict.json.

    Read-modify-write so bench_predict and bench_rpc each own a section
    without clobbering the other.
    """
    data: Dict[str, Any] = {}
    if os.path.exists(BENCH_JSON):
        try:
            with open(BENCH_JSON) as f:
                data = json.load(f)
        except Exception:
            data = {}
    data[section] = payload
    os.makedirs(REPORT_DIR, exist_ok=True)
    with open(BENCH_JSON, "w") as f:
        json.dump(data, f, indent=2, sort_keys=True)
        f.write("\n")
    print(f"# wrote {section} → {os.path.abspath(BENCH_JSON)}")
