"""The trace reduction, on a small trace recorded on a TPU v5e: one
`LatencyService.predict_batch` of 160 paper_nas_224 graphs, whose
large per-type calls ran the fused traversal on the chip.  The fixture
holds what `chipbench.trace.extract` kept of the xplane file, the
marker's ``perf_counter`` reading, the window, and the service's spans.
"""
import json
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

from chipbench import spec  # noqa: E402
from chipbench.record import Run  # noqa: E402
from chipbench.trace import FUSED_PROGRAM, DeviceTrace  # noqa: E402

FIXTURE = os.path.join(os.path.dirname(__file__), "data",
                       "trace_v5e_predict_batch.json")


@pytest.fixture(scope="module")
def recorded():
    with open(FIXTURE) as f:
        d = json.load(f)
    trace = DeviceTrace.from_events(d["events"], d["mark_pc"], d["t0"],
                                    d["t1"])
    return d, trace


def test_busy_time_is_the_ops_inside_the_window(recorded):
    d, trace = recorded
    (dev,) = trace.devices
    ops = trace.devices[dev]["ops"]
    assert ops and all(d["t0"] <= a < b <= d["t1"] for _, a, b in ops)
    # A while op's body ops are listed inside it, so busy time is the
    # union: count the elementary intervals that some op covers.
    edges = sorted({x for _, a, b in ops for x in (a, b)})
    covered = sum(hi - lo for lo, hi in zip(edges, edges[1:])
                  if any(a <= lo and hi <= b for _, a, b in ops))
    assert trace.busy_s == pytest.approx(covered, rel=1e-9)
    assert max(b - a for _, a, b in ops) <= trace.busy_s
    assert trace.busy_s < sum(b - a for _, a, b in ops)
    assert 0 < trace.busy_s < d["t1"] - d["t0"]


def test_fused_runs_fall_inside_their_service_kernel_spans(recorded):
    d, trace = recorded
    fused = [(a, b) for lines in trace.devices.values()
             for name, a, b in lines["modules"] if FUSED_PROGRAM in name]
    kernels = [s for s in d["spans"] if s["name"] == "service.kernel"
               and s["attrs"].get("fused")]
    assert len(fused) == len(kernels) > 0
    slack = 100e-6      # the marker's own width and clock reads
    for a, b in fused:
        assert any(k["start"] - slack <= a and b <= k["end"] + slack
                   for k in kernels), (a, b)
    assert trace.program_s(FUSED_PROGRAM) == pytest.approx(
        sum(b - a for a, b in fused))


def test_breakdown_names_programs_and_gaps(recorded):
    d, trace = recorded
    out = trace.breakdown(d["spans"], [])
    ops, gaps = out["device_ops"], out["idle_gaps"]
    assert 0 < len(ops) <= 10 and 0 < len(gaps) <= 10
    assert [v for _, v in ops] == sorted((v for _, v in ops), reverse=True)
    assert ops[0][0].startswith("jit__fused_core/")
    names = {s["name"] for s in d["spans"]} | {"no span"}
    assert all(label in names for label, _ in gaps)


def test_roofline_share_of_the_recorded_calls(recorded):
    d, trace = recorded
    run = Run(kind="search", cell="", chips=1, setup_s=0.0, t0=d["t0"],
              t1=d["t1"])
    run.spans, run.trace, run.cands = d["spans"], trace, 160
    run.peak = spec.peaks("TPU v5 lite")
    # The bank the fixture was recorded with: 150 trees a type.
    run.bank_shapes = {t: {"trees": 150, "depth": depth, "features": f,
                           "bank_bytes": 20 * n + 150 * 4}
                       for t, f, n, depth in d["bank"]}
    share = spec.reader("tree_fused_roofline")(run)
    assert 0 < share <= 100
    assert spec.reader("tree.device_ms_per_kcand.search")(run) > 0
