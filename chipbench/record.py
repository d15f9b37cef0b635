"""What one run hands to the metric readers."""
from __future__ import annotations

from typing import Any, Dict, List, Optional, Tuple


def _sum_series(snap: Dict[str, Any], kind: str, name: str) -> Any:
    return snap.get(kind, {}).get(name, {})


class Run:
    """One run's window: host-clock times, counts, spans, registry
    snapshots, compile events and (traced runs) the device trace.

    Times are ``time.perf_counter`` seconds; ``t0``/``t1`` bound the
    measured window.
    """

    def __init__(self, *, kind: str, cell: str, chips: int, setup_s: float,
                 t0: float, t1: float):
        self.kind = kind
        self.cell = cell
        self.chips = chips
        self.setup_s = setup_s
        self.t0 = t0
        self.t1 = t1
        self.cands = 0                       # search: candidates scored
        self.attempted = 0
        self.failed = 0
        self.spans: List[Dict[str, Any]] = []
        self.annotations: List[Tuple[str, float, float]] = []
        self.reg_before: Dict[str, Any] = {}
        self.reg_after: Dict[str, Any] = {}
        self.compile: Dict[str, Any] = {}
        self.trace: Optional[Any] = None     # trace.DeviceTrace
        self.bank_shapes: Dict[str, Dict[str, int]] = {}
        self.peak: Optional[Dict[str, Any]] = None
        self.memory_peak_bytes = 0
        self.notes: Dict[str, Any] = {}

    @property
    def window_s(self) -> float:
        return self.t1 - self.t0

    # -- registry deltas over the window ------------------------------------
    def counter(self, name: str) -> float:
        def total(snap):
            return sum(float(v) for v in
                       _sum_series(snap, "counters", name).values())
        return total(self.reg_after) - total(self.reg_before)

    def hist(self, name: str) -> Tuple[float, int]:
        """(sum, count) added to histogram ``name`` in the window."""
        def total(snap):
            series = _sum_series(snap, "histograms", name).values()
            return (sum(float(h["sum"]) for h in series),
                    sum(int(h["count"]) for h in series))
        (s1, c1), (s0, c0) = total(self.reg_after), total(self.reg_before)
        return s1 - s0, c1 - c0

    # -- spans ----------------------------------------------------------------
    def spans_named(self, name: str) -> List[Dict[str, Any]]:
        """Finished spans of ``name`` that overlap the window."""
        return [s for s in self.spans
                if s["name"] == name and s["end"] is not None
                and s["end"] > self.t0 and s["start"] < self.t1]

    def clipped(self, span: Dict[str, Any]) -> float:
        return max(0.0, min(span["end"], self.t1) - max(span["start"], self.t0))

    def kernel_calls(self) -> List[Dict[str, Any]]:
        """Per-op-type predictor calls that started in the window."""
        return [s["attrs"] for s in self.spans_named("service.kernel")
                if self.t0 <= s["start"] < self.t1 and "rows" in s["attrs"]]
