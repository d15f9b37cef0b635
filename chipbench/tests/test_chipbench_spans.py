"""The readers of the scoring path's spans, on a hand-built run: the
fingerprint and featurization shares of the window, and the tree
kernel layer's host time per 1,000 candidates against a hand-built
device trace.  Each reader gives None where its spans are absent, as in
a run of a program that does not record them."""
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

from chipbench import spec  # noqa: E402
from chipbench.record import Run  # noqa: E402
from chipbench.trace import DeviceTrace  # noqa: E402

READERS = ("service.fingerprint_share_pct.search",
           "service.featurize_share_pct.search",
           "tree.host_ms_per_kcand.search")


def _span(name, start, end, **attrs):
    return {"name": name, "tid": "t", "sid": f"s{start}", "parent": None,
            "start": start, "end": end, "status": "ok", "attrs": attrs}


def _run(spans, devices=None, cands=2000):
    """A 10 s window from t=100, with ``devices`` as the busy op
    intervals of each chip."""
    run = Run(kind="search", cell="", chips=1, setup_s=0.0, t0=100.0,
              t1=110.0)
    run.spans, run.cands = spans, cands
    if devices is not None:
        run.trace = DeviceTrace(
            {d: {"modules": [], "ops": [("op", a, b) for a, b in ops]}
             for d, ops in devices.items()}, run.t0, run.t1)
    return run


@pytest.mark.parametrize("name,metric", [
    ("service.fingerprint", "service.fingerprint_share_pct.search"),
    ("service.featurize", "service.featurize_share_pct.search")])
def test_service_share_clips_spans_to_the_window(name, metric):
    spans = [_span(name, 99.0, 100.5),            # 0.5 s inside
             _span(name, 103.0, 104.0),           # 1 s
             _span(name, 109.75, 111.0),          # 0.25 s inside
             _span(name, 111.0, 112.0),           # after the window
             _span("service.predict_batch", 104.0, 109.0)]
    assert spec.reader(metric)(_run(spans)) == pytest.approx(17.5)


def test_tree_host_time_leaves_out_busy_device_time():
    spans = [_span("tree.stage", 99.0, 101.0),    # 1 s inside the window
             _span("tree.dispatch", 101.0, 101.5),
             _span("tree.wait", 101.5, 103.0),    # union: 100 .. 103
             _span("tree.stage", 105.0, 106.0),   # union: 105 .. 106
             _span("service.kernel", 100.0, 108.0)]
    # Chip 0 is busy 100.5 .. 102.5 (2 s of the 4 s union); chip 1 is
    # busy 102.5 .. 105.5 (1 s of it: 102.5 .. 103 and 105 .. 105.5).
    devices = {"0": [(100.5, 101.5), (101.0, 102.5)],
               "1": [(102.5, 105.5), (107.0, 109.0)]}
    got = spec.reader("tree.host_ms_per_kcand.search")(_run(spans, devices))
    # Host-only time: 2 s on chip 0, 3 s on chip 1, 2.5 s on average,
    # over 2,000 candidates.
    assert got == pytest.approx(1e3 * 2.5 / 2.0)


def test_tree_host_time_without_busy_time_is_the_whole_union():
    spans = [_span("tree.wait", 100.0, 100.25), _span("tree.stage", 100.1,
                                                       100.5)]
    got = spec.reader("tree.host_ms_per_kcand.search")(
        _run(spans, {"0": []}, cands=500))
    assert got == pytest.approx(1e3 * 0.5 / 0.5)


@pytest.mark.parametrize("metric", READERS)
def test_reader_is_none_without_its_spans(metric):
    other = [_span("service.predict_batch", 101.0, 102.0),
             _span("service.kernel", 101.0, 101.5, op_type="conv2d",
                   backend="jax", rows=10, fused=True)]
    assert spec.reader(metric)(_run(other, {"0": [(101.0, 101.2)]})) is None


def test_tree_host_time_is_none_without_a_trace_or_candidates():
    read = spec.reader("tree.host_ms_per_kcand.search")
    spans = [_span("tree.stage", 101.0, 102.0)]
    assert read(_run(spans)) is None
    assert read(_run(spans, {"0": []}, cands=0)) is None


def test_readers_are_listed_for_both_search_cells():
    bench = spec.benchmark()
    for cell in ("search.paper_nas_224", "search.randwire_ws_224"):
        names = {m["name"] for m in spec.cell_metrics(bench, cell,
                                                      "per_layer")}
        assert set(READERS) <= names
