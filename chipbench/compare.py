"""The comparison that decides ``correct``.

Every report the timed path returned in the window is compared with
the float64 reference of the same graph, featurized by the
configuration's reference table (`chipbench.spec.reference_features`):

* ``e2e_gap``: the widest relative gap of a report's end-to-end
  seconds from the reference's;
* ``op_gap``: the widest gap of one op's prediction from the
  reference's, scaled into the report's end-to-end seconds
  (``op_sum_scale * |p - r| / e2e_ref``), so a tiny op's rounding does
  not read as a large relative error;
* ``structure``: reports whose fingerprint, kernel count or per-op
  type list differ from the reference's (exact: limit 0);
* ``unanswered``: requests that never got a report (exact: limit 0).
"""
from __future__ import annotations

from typing import Any, Dict, List, Optional, Tuple

import numpy as np

from chipbench.reference import FeatureTable, ReferenceBank, predict_graphs

NUMBERS = ("e2e_gap", "op_gap", "structure", "unanswered")


class Answers:
    """The window's reports in compact form, with one graph kept per
    graph name: a long window repeats the same candidates, and keeping
    every graph object grows the heap until the collector stalls the
    timed path."""

    def __init__(self) -> None:
        self.graphs: Dict[str, Any] = {}      # name → OpGraph or JSON
        self.types: Dict[str, Tuple[str, ...]] = {}
        self.rows: List[Tuple[str, str, int, Optional[Tuple[str, ...]],
                              np.ndarray, float]] = []

    def add(self, graph: Any, report: Any) -> None:
        name = graph["name"] if isinstance(graph, dict) else graph.name
        types = tuple(t for t, _ in report.per_op)
        if name not in self.graphs:
            self.graphs[name] = graph
            self.types[name] = types
        self.rows.append((name, report.fingerprint, report.num_kernels,
                          None if types == self.types[name] else types,
                          np.array([v for _, v in report.per_op]),
                          report.e2e_s))

    def __len__(self) -> int:
        return len(self.rows)


def readings(bank: ReferenceBank, features: FeatureTable, answers: Answers,
             unanswered: int = 0, precision: str = "float64",
             against: str = "program") -> Dict[str, float]:
    """The numbers for ``answers``, their op features computed by
    ``features``.

    ``against="program"`` compares the program's reports; ``"control"``
    puts the reference computed in ``precision`` in the program's place
    (the control run of the comparison)."""
    names = list(answers.graphs)
    graphs = [g if isinstance(g, dict) else g.to_json()
              for g in (answers.graphs[n] for n in names)]
    ref = dict(zip(names, predict_graphs(bank, graphs, features)))
    ctl = dict(zip(names, predict_graphs(bank, graphs, features, precision))) \
        if against == "control" else {}
    e2e_gap = op_gap = 0.0
    structure = 0
    for name, fp, nk, types, values, e2e in answers.rows:
        r = ref[name]
        if against == "control":
            c = ctl[name]
            fp, nk, e2e = c["fingerprint"], c["num_kernels"], c["e2e_s"]
            types = tuple(t for t, _ in c["per_op"])
            values = np.array([v for _, v in c["per_op"]])
        elif types is None:
            types = answers.types[name]
        want = np.array([v for _, v in r["per_op"]])
        if (fp != r["fingerprint"] or nk != r["num_kernels"]
                or list(types) != [t for t, _ in r["per_op"]]
                or len(values) != len(want)):
            structure += 1
            continue
        e2e_gap = max(e2e_gap, abs(e2e - r["e2e_s"]) / r["e2e_s"])
        if len(want):
            op_gap = max(op_gap, float(np.max(np.abs(values - want)))
                         * bank.op_sum_scale / r["e2e_s"])
    return {"e2e_gap": e2e_gap, "op_gap": op_gap,
            "structure": float(structure), "unanswered": float(unanswered)}


def judge(numbers: Dict[str, float], limits: Dict[str, float]) -> bool:
    return all(numbers[k] <= limits[k] for k in NUMBERS)
