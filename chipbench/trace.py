"""Device time from the JAX profiler's trace, on the host's clock.

A traced window runs under `jax.profiler`; at its start one marker
annotation is written while ``time.perf_counter`` is read, which puts
the trace's timestamps on the clock of the program's spans.  A TPU
device plane holds an "XLA Modules" line (one event per program run,
named ``<jit name>(<fingerprint>)``) and an "XLA Ops" line (one event
per HLO op, named by its HLO text).  The reduction keeps both:

* busy seconds: the union of op intervals inside the window, averaged
  over the chips used;
* a program's device seconds: the time of program runs whose name
  contains a pattern, summed over chips;
* the breakdown: the device programs that took most time, and the
  longest idle gaps, each named by the innermost program span or
  harness annotation open at its middle.
"""
from __future__ import annotations

import bisect
import glob
import os
import re
import time
from typing import Any, Dict, List, Optional, Sequence, Tuple

MARKER = "chipbench.clock"
# The fused traversal's jitted core (`repro.kernels.tree_gather._fused_core`).
FUSED_PROGRAM = "_fused_core"
_DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
_LINES = {"XLA Modules": "modules", "XLA Ops": "ops"}
BREAKDOWN_ENTRIES = 10


class DeviceTracer:
    """Starts and stops the profiler around a window, with the marker."""

    def __init__(self, log_dir: str):
        self.log_dir = log_dir
        self.mark_pc: Optional[float] = None

    def start(self) -> None:
        import jax

        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 1
        jax.profiler.start_trace(self.log_dir, profiler_options=opts)
        with jax.profiler.TraceAnnotation(MARKER):
            a = time.perf_counter()
            b = time.perf_counter()
        self.mark_pc = 0.5 * (a + b)

    def stop(self) -> None:
        import jax

        jax.profiler.stop_trace()

    def path(self) -> str:
        found = glob.glob(os.path.join(self.log_dir, "**", "*.xplane.pb"),
                          recursive=True)
        if len(found) != 1:
            raise RuntimeError(f"expected one trace file, found {found}")
        return found[0]

    def reduce(self, t0: float, t1: float) -> "DeviceTrace":
        return DeviceTrace.from_events(extract(self.path()), self.mark_pc,
                                       t0, t1)


def _short(line_kind: str, name: str) -> str:
    """``jit__fused_core(123)`` → ``jit__fused_core``; an op's HLO text
    ``%while.3 = (...) while(...)`` → ``while.3``."""
    if line_kind == "modules":
        return name.split("(", 1)[0]
    return name.split(" = ", 1)[0].lstrip("%")


def extract(path: str) -> Dict[str, Any]:
    """The marker's time and each TPU plane's program runs and ops, from
    an xplane file: ``{"marker_ns": float, "devices": {index: {"modules":
    [[name, start_ns, duration_ns], ...], "ops": [...]}}}``."""
    import jax

    pd = jax.profiler.ProfileData.from_file(path)
    marker_ns = None
    devices: Dict[str, Dict[str, List[List[Any]]]] = {}
    for plane in pd.planes:
        m = _DEVICE_PLANE.match(plane.name)
        for line in plane.lines:
            if m is None:
                for ev in line.events:
                    if ev.name == MARKER:
                        marker_ns = ev.start_ns + 0.5 * ev.duration_ns
                continue
            kind = _LINES.get(line.name)
            if kind is None:
                continue
            dev = devices.setdefault(m.group(1), {"modules": [], "ops": []})
            dev[kind].extend([_short(kind, ev.name), float(ev.start_ns),
                              float(ev.duration_ns)] for ev in line.events)
    if marker_ns is None:
        raise RuntimeError(f"no {MARKER} annotation in {path}")
    return {"marker_ns": marker_ns, "devices": devices}


def _union(intervals: List[Tuple[float, float]]) -> List[Tuple[float, float]]:
    out: List[Tuple[float, float]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1] = (out[-1][0], max(out[-1][1], b))
        else:
            out.append((a, b))
    return out


class DeviceTrace:
    """Program runs and ops on the ``perf_counter`` clock, clipped to a
    window."""

    def __init__(self, devices: Dict[str, Dict[str, List[Tuple[str, float,
                                                               float]]]],
                 t0: float, t1: float):
        self.devices = devices  # index → {"modules"|"ops": [(name, a, b)]}
        self.t0, self.t1 = t0, t1

    @classmethod
    def from_events(cls, events: Dict[str, Any], mark_pc: float, t0: float,
                    t1: float) -> "DeviceTrace":
        base = mark_pc - events["marker_ns"] * 1e-9
        devices = {}
        for dev, lines in events["devices"].items():
            devices[dev] = {}
            for kind, evs in lines.items():
                kept = []
                for name, start_ns, dur_ns in evs:
                    a = base + start_ns * 1e-9
                    a, b = max(a, t0), min(a + dur_ns * 1e-9, t1)
                    if b > a:
                        kept.append((name, a, b))
                devices[dev][kind] = kept
        return cls(devices, t0, t1)

    def busy_intervals(self, dev: str) -> List[Tuple[float, float]]:
        return _union([(a, b) for _, a, b in self.devices[dev]["ops"]])

    @property
    def busy_s(self) -> float:
        if not self.devices:
            return 0.0
        return sum(sum(b - a for a, b in self.busy_intervals(d))
                   for d in self.devices) / len(self.devices)

    def program_s(self, pattern: str) -> float:
        """Device seconds of program runs whose name contains
        ``pattern``, summed over chips."""
        return sum(b - a for lines in self.devices.values()
                   for name, a, b in lines["modules"] if pattern in name)

    def breakdown(self, spans: Sequence[Dict[str, Any]],
                  annotations: Sequence[Tuple[str, float, float]]
                  ) -> Dict[str, List[List[Any]]]:
        by_op: Dict[str, float] = {}
        for lines in self.devices.values():
            modules = sorted(lines["modules"], key=lambda m: m[1])
            starts = [m[1] for m in modules]
            for op, a, b in lines["ops"]:
                i = bisect.bisect_right(starts, a) - 1
                inside = i >= 0 and a < modules[i][2]
                key = f"{modules[i][0]}/{op}" if inside else op
                by_op[key] = by_op.get(key, 0.0) + (b - a)
        top = sorted(by_op.items(), key=lambda kv: -kv[1])
        gaps = []
        for dev in sorted(self.devices):
            t = self.t0
            for a, b in self.busy_intervals(dev) + [(self.t1, self.t1)]:
                if a > t:
                    gaps.append((a - t, t, a))
                t = max(t, b)
        gaps.sort(key=lambda g: -g[0])
        opened = [(s["name"], s["start"], s["end"]) for s in spans
                  if s.get("end") is not None] + list(annotations)
        named = []
        for length, a, b in gaps[:BREAKDOWN_ENTRIES]:
            mid = 0.5 * (a + b)
            inner = [o for o in opened if o[1] <= mid < o[2]]
            label = max(inner, key=lambda o: o[1])[0] if inner else "no span"
            named.append([label, length])
        return {"device_ops": [[k, v] for k, v in top[:BREAKDOWN_ENTRIES]],
                "idle_gaps": named}
