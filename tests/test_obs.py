"""`repro.obs` — observability layer: metrics, tracing, drift, export.

Covers histogram correctness (fixed log-spaced boundaries, quantile
estimates within one bucket of numpy's), Welford accumulators against
two-pass statistics, bit-stable registry snapshots, deterministic span
ids + parenting + the wire `trace` field (committed golden bytes), the
flight recorder's schema-stable fault dumps, the `metrics` RPC
endpoint (JSON + Prometheus), conservation of request counts under a
32-thread socket flood, and full bit-identical replay of a seeded
workload (snapshot AND span tree).  The `warmup=0` timing regression
rides along (utils/timing honored `max(1, warmup)` before).
"""
import json
import os
import threading

import numpy as np
import pytest

from repro.core.dataset import synthetic_graphs
from repro.core.features import clear_graph_feature_cache
from repro.core.nas_space import NASSpaceConfig, sample_architecture
from repro.core.profiler import DeviceSetting, ProfileSession
from repro.obs import (DEFAULT_SIZE_BUCKETS, DriftMonitor, FlightRecorder,
                       MetricsRegistry, Observability, Tracer, Welford,
                       attach_session_drift, log_buckets, to_prometheus,
                       validate_dump)
from repro.pipeline import LatencyService, PredictorHub, ProfileStore
from repro.rpc.batcher import BatchPolicy, ManualClock, MicroBatcher
from repro.rpc.chaos import FaultPlan, FaultSpec
from repro.rpc.client import LatencyClient
from repro.rpc.protocol import (RPCError, decode_request, decode_response,
                                encode_request, encode_response)
from repro.rpc.server import LatencyRPCServer
from repro.transfer import CostModelProfileSession
from repro.utils.timing import time_callable, time_sequential

GOLDEN = os.path.join(os.path.dirname(__file__), "golden")
ITERS = int(os.environ.get("RPC_CHAOS_ITERS", "20"))
SOURCE = DeviceSetting("cpu_f32", "float32", "op_by_op")
SPACE = NASSpaceConfig(resolution=16)


def graphs_for(seeds):
    return [sample_architecture(s, SPACE) for s in seeds]


def build_serving(seed=3):
    """Fresh cost-model store + trained hub + service (no shared state,
    so counter-conservation asserts are exact)."""
    store = ProfileStore()
    session = CostModelProfileSession(store=store, seed=seed)
    for g in synthetic_graphs(8, resolution=16):
        session.profile_graph(g, SOURCE)
    hub = PredictorHub()
    hub.train(store, SOURCE, "gbdt", hparams={"n_stages": 20}, min_samples=3)
    svc = LatencyService(hub, default_setting=SOURCE, predictor="gbdt")
    return store, hub, svc


@pytest.fixture(scope="module")
def served():
    store, hub, svc = build_serving()
    return {"store": store, "hub": hub, "service": svc}


# ---------------------------------------------------------------------------
# Histograms: boundaries, conservation, quantiles vs numpy
# ---------------------------------------------------------------------------

class TestHistogram:
    def test_log_buckets_deterministic_and_validated(self):
        b = log_buckets(1e-6, 10.0, 43)
        assert b == log_buckets(1e-6, 10.0, 43)
        assert len(b) == 43 and b[0] == 1e-6 and abs(b[-1] - 10.0) < 1e-12
        assert all(x < y for x, y in zip(b, b[1:]))
        for bad in ((0, 1, 4), (1, 1, 4), (1e-3, 1.0, 1)):
            with pytest.raises(ValueError):
                log_buckets(*bad)

    def test_observe_conserves_count_and_sum(self):
        reg = MetricsRegistry()
        reg.histogram("h", buckets=(1.0, 2.0, 4.0))
        for v in (0.5, 1.0, 1.5, 3.0, 100.0):       # under, edge, mid, over
            reg.observe("h", v)
        st = reg.hist_stats("h")
        assert st["count"] == 5 and st["sum"] == 106.0
        assert st["min"] == 0.5 and st["max"] == 100.0
        snap = reg.snapshot(include_collected=False)
        h = snap["histograms"]["h"][""]
        assert sum(h["counts"]) == h["count"] == 5
        # (..,1] gets 0.5 and 1.0; (1,2] gets 1.5; (2,4] gets 3.0;
        # overflow gets 100.
        assert h["counts"] == [2, 1, 1, 1]

    @pytest.mark.parametrize("q", [0.5, 0.9, 0.99])
    def test_quantile_within_one_bucket_of_numpy(self, q):
        rng = np.random.default_rng(11)
        vals = np.exp(rng.normal(-6.0, 1.5, size=4000))    # lognormal seconds
        reg = MetricsRegistry()
        edges = log_buckets(1e-6, 10.0, 43)
        reg.histogram("lat", buckets=edges)
        for v in vals:
            reg.observe("lat", float(v))
        est = reg.hist_quantile("lat", q)
        exact = float(np.quantile(vals, q))
        # The estimate must land inside the bucket containing the exact
        # quantile (or one of its neighbours): error < one bucket width.
        idx = int(np.searchsorted(edges, exact))
        lo = edges[max(idx - 1, 0)]
        hi = edges[min(idx + 1, len(edges) - 1)]
        assert lo <= est <= hi, (q, est, exact)

    def test_quantile_degenerate_cases(self):
        reg = MetricsRegistry()
        reg.histogram("h")
        assert reg.hist_quantile("h", 0.5) == 0.0        # empty
        reg.observe("h", 0.01)
        assert reg.hist_quantile("h", 0.5) == pytest.approx(0.01)
        reg2 = MetricsRegistry()
        reg2.histogram("g")
        for _ in range(10):
            reg2.observe("g", 2.5e-3)                    # all one bucket
        assert reg2.hist_quantile("g", 0.99) == pytest.approx(2.5e-3)


# ---------------------------------------------------------------------------
# Registry: labels, kinds, bit-stable snapshots
# ---------------------------------------------------------------------------

class TestRegistry:
    def test_counters_gauges_labels(self):
        reg = MetricsRegistry()
        reg.inc("req_total", batcher="b0")
        reg.inc("req_total", 2, batcher="b1")
        reg.inc("req_total", batcher="b0")
        assert reg.get("req_total", batcher="b0") == 2
        assert reg.total("req_total") == 4
        assert reg.labeled_values("req_total", "batcher") == \
            {"b0": 2.0, "b1": 2.0}
        reg.set("depth", 7, batcher="b0")
        reg.set_max("depth", 3, batcher="b0")            # lower: keeps 7
        assert reg.get("depth", batcher="b0") == 7

    def test_kind_conflict_raises(self):
        reg = MetricsRegistry()
        reg.counter("x_total")
        with pytest.raises(ValueError):
            reg.gauge("x_total")

    def test_instance_ids_deterministic(self):
        reg = MetricsRegistry()
        assert [reg.instance("batcher") for _ in range(2)] == \
            ["batcher0", "batcher1"]
        assert reg.instance("client") == "client0"

    def test_snapshot_bit_stable_across_identical_runs(self):
        def drive(reg):
            reg.inc("a_total", 3, k="x")
            reg.set("g", 1.0)                     # integral float → int
            reg.histogram("h", buckets=(1.0, 2.0))
            reg.observe("h", 1.5)
        r1, r2 = MetricsRegistry(), MetricsRegistry()
        drive(r1), drive(r2)
        assert r1.snapshot_json() == r2.snapshot_json()
        snap = r1.snapshot()
        assert snap["gauges"]["g"][""] == 1                # int, not 1.0
        assert isinstance(snap["gauges"]["g"][""], int)

    def test_collector_joins_snapshot_and_errors_are_contained(self):
        reg = MetricsRegistry()
        reg.collect("comp", lambda: {"n": np.int64(3), "x": (1, 2)})
        reg.collect("boom", lambda: 1 / 0)
        snap = reg.snapshot()
        assert snap["collected"]["comp"] == {"n": 3, "x": [1, 2]}
        assert "ZeroDivisionError" in snap["collected"]["boom"]["error"]
        json.dumps(snap)                                  # pure JSON

    def test_snapshot_roundtrip_property(self):
        hyp = pytest.importorskip("hypothesis")
        st = pytest.importorskip("hypothesis.strategies")

        @hyp.given(st.lists(st.tuples(
            st.sampled_from(["a_total", "b_total"]),
            st.integers(min_value=1, max_value=5),
            st.sampled_from(["x", "y"])), max_size=20))
        @hyp.settings(deadline=None, max_examples=50)
        def prop(ops):
            reg = MetricsRegistry()
            for name, v, lbl in ops:
                reg.inc(name, v, k=lbl)
            text = reg.snapshot_json()
            assert json.dumps(json.loads(text), sort_keys=True,
                              separators=(",", ":")) == text

        prop()


# ---------------------------------------------------------------------------
# Welford accumulators vs two-pass statistics
# ---------------------------------------------------------------------------

class TestWelford:
    def test_matches_two_pass(self):
        rng = np.random.default_rng(5)
        xs = rng.normal(3.0, 0.7, size=500)
        w = Welford()
        for x in xs:
            w.add(float(x))
        assert w.n == 500
        assert w.mean == pytest.approx(float(np.mean(xs)), abs=1e-12)
        assert w.variance() == pytest.approx(float(np.var(xs)), rel=1e-10)

    def test_merge_equals_combined(self):
        rng = np.random.default_rng(6)
        a, b = rng.normal(size=64), rng.normal(2.0, 3.0, size=100)
        wa, wb, wall = Welford(), Welford(), Welford()
        for x in a:
            wa.add(float(x)), wall.add(float(x))
        for x in b:
            wb.add(float(x)), wall.add(float(x))
        m = wa.merge(wb)
        assert m.n == wall.n
        assert m.mean == pytest.approx(wall.mean, abs=1e-12)
        assert m.variance() == pytest.approx(wall.variance(), rel=1e-10)

    def test_json_roundtrip(self):
        w = Welford()
        for x in (1.0, 2.0, 4.0):
            w.add(x)
        again = Welford.from_json(w.to_json())
        assert (again.n, again.mean, again.m2) == (w.n, w.mean, w.m2)


# ---------------------------------------------------------------------------
# Tracer: deterministic ids, parenting, wire context
# ---------------------------------------------------------------------------

class TestTracer:
    def test_ids_deterministic_and_nested_parenting(self):
        def run():
            t = Tracer(clock=ManualClock(), seed=9)
            with t.span("outer") as outer:
                with t.span("inner"):
                    pass
                t.event("point", attrs={"k": 1})
            return t.export(), outer
        spans1, outer1 = run()
        spans2, _ = run()
        assert spans1 == spans2                          # bit-identical
        by_name = {s["name"]: s for s in spans1}
        assert by_name["inner"]["parent"] == outer1.span_id
        assert by_name["point"]["parent"] == outer1.span_id
        assert by_name["inner"]["tid"] == by_name["outer"]["tid"]
        assert by_name["outer"]["parent"] is None

    def test_wire_context_propagates_trace(self):
        t1 = Tracer(seed=1)
        t2 = Tracer(seed=2)
        client_span = t1.start_span("send")
        ctx = t1.wire_context(client_span)
        server_span = t2.start_span("dispatch", trace=ctx)
        assert server_span.trace_id == client_span.trace_id
        assert server_span.parent_id == client_span.span_id

    def test_disabled_tracer_is_noop_and_off_the_wire(self):
        t = Tracer(enabled=False)
        sp = t.start_span("x")
        sp.set_attr("a", 1).end()
        assert t.wire_context(sp) is None
        assert t.export() == []

    def test_activate_sets_ambient_without_ending(self):
        t = Tracer(seed=3)
        sp = t.start_span("parent")
        with t.activate(sp):
            child = t.start_span("child")
        assert child.parent_id == sp.span_id
        assert sp.end_at is None                          # still open
        sp.end()

    def test_export_bounded_by_capacity(self):
        t = Tracer(seed=4, capacity=8)
        for i in range(20):
            t.event(f"e{i}")
        names = [s["name"] for s in t.export()]
        assert names == [f"e{i}" for i in range(12, 20)]


# ---------------------------------------------------------------------------
# Flight recorder: schema-stable fault dumps
# ---------------------------------------------------------------------------

class TestFlightRecorder:
    def test_dump_schema_and_bounds(self):
        rec = FlightRecorder(capacity=4, max_dumps=2)
        t = Tracer(clock=ManualClock(), seed=0, recorder=rec)
        for i in range(10):
            t.event(f"e{i}")
        assert len(rec.spans()) == 4                     # ring bounded
        for r in ("one", "two", "three"):
            rec.dump(r, {"k": 1})
        assert len(rec.dumps) == 2                       # dumps bounded
        d = rec.last_dump()
        assert d["reason"] == "three"
        validate_dump(d)
        assert rec.stats()["last_reason"] == "three"

    @pytest.mark.parametrize("bad", [
        "not a dict", {"reason": "", "attrs": {}, "spans": []},
        {"reason": "r", "attrs": {}, "spans": [{}]},
        {"reason": "r", "attrs": {}, "spans": [
            {"name": "n", "tid": "t", "sid": "s", "parent": None,
             "start": 0, "end": 1, "status": "meh", "attrs": {}}]},
    ])
    def test_validate_rejects_malformed(self, bad):
        with pytest.raises(ValueError):
            validate_dump(bad)


# ---------------------------------------------------------------------------
# Drift monitor
# ---------------------------------------------------------------------------

class TestDrift:
    def test_perfect_predictions_score_zero(self):
        m = DriftMonitor(threshold=0.25, min_count=4)
        for _ in range(10):
            m.observe("dev", "conv2d", 0.01, 0.01)
        assert m.score() == 0.0
        assert m.drifted() == []

    def test_systematic_2x_slowdown_flags(self):
        m = DriftMonitor(threshold=0.25, min_count=4)
        for _ in range(10):
            m.observe("dev", "conv2d", 0.01, 0.02)       # observed 2× slower
        cell = m.cell("dev", "conv2d")
        assert cell.mean == pytest.approx(np.log(2.0), abs=1e-9)
        assert m.score() == pytest.approx(np.log(2.0) / 0.25)
        assert m.drifted() == [("dev", "conv2d",
                                pytest.approx(np.log(2.0) / 0.25))]

    def test_min_count_gates_scoring(self):
        m = DriftMonitor(threshold=0.1, min_count=8)
        for _ in range(7):                               # one short
            m.observe("dev", "dense", 0.01, 0.05)
        assert m.score() == 0.0
        m.observe("dev", "dense", 0.01, 0.05)
        assert m.score() > 1.0

    def test_snapshot_and_reset(self):
        m = DriftMonitor(min_count=2)
        m.observe("a", "conv2d", 0.01, 0.01)
        m.observe("a", "conv2d", 0.01, 0.01)
        snap = m.snapshot()
        assert snap["observations"] == 2
        assert "a|conv2d" in snap["cells"]
        json.dumps(snap)
        m.reset()
        assert m.snapshot()["observations"] == 0

    def test_serve_engine_feeds_drift_and_registry(self):
        import jax.numpy as jnp
        from repro.serving.engine import ServeEngine

        class StubModel:
            def init_cache(self, slots, max_len):
                return {}

            def decode_step(self, params, batch, cache):
                return jnp.zeros((batch["token"].shape[0], 4)), cache

        obs = Observability(seed=1)
        eng = ServeEngine(StubModel(), {}, batch_slots=2, obs=obs)
        eng.predicted_step_s = 1.0               # wildly optimistic
        eng.submit(np.array([1, 2], np.int32), max_new_tokens=2)
        eng.run(max_steps=8)
        st = eng.stats()
        assert st["steps"] == obs.registry.get("serve_steps_total",
                                               engine="engine0") > 0
        cell = obs.drift.cell("serve", "decode_step")
        assert cell is not None and cell.n == st["steps"]
        assert cell.mean < 0                     # observed ≪ predicted

    def test_attach_session_drift_taps_fresh_measurements(self, served):
        monitor = DriftMonitor(min_count=1)
        store, svc = served["store"], served["service"]
        session = CostModelProfileSession(store=ProfileStore(), seed=3)
        attach_session_drift(session, svc, monitor)
        g = graphs_for([321])[0]
        session.profile_graph(g, SOURCE)
        snap = monitor.snapshot()
        assert snap["observations"] > 0
        # Cost-model "measurements" against a hub trained on the same
        # cost model: residuals are small, nothing drifts.
        assert all(c["n"] >= 1 for c in snap["cells"].values())


# ---------------------------------------------------------------------------
# Timing regression: warmup=0 must mean zero warm-up runs
# ---------------------------------------------------------------------------

class TestTimingWarmup:
    def test_time_callable_honors_warmup_zero(self):
        calls = []
        time_callable(lambda: calls.append(1), warmup=0, inner=2, repeats=1)
        assert len(calls) == 2                           # timed runs only
        calls.clear()
        time_callable(lambda: calls.append(1), warmup=3, inner=2, repeats=1)
        assert len(calls) == 5

    def test_time_sequential_honors_warmup_zero(self):
        calls = []
        time_sequential([(lambda: calls.append(1), ())],
                        warmup=0, inner=2, repeats=1)
        assert len(calls) == 2


# ---------------------------------------------------------------------------
# Wire: traced request/response golden bytes + endpoint behaviour
# ---------------------------------------------------------------------------

class _StubService:
    predictor = "gbdt"
    default_setting = None

    def available(self):
        return [("float32/op_by_op", "gbdt")]

    def stats(self):
        return {"predict_batch_calls": 0}


class TestTracedWire:
    def test_traced_golden_bytes(self):
        """Committed traced pair: canonical re-encode AND a live server
        reproduces the exact response bytes (echoed client trace id,
        server span id)."""
        with open(os.path.join(GOLDEN, "rpc_traced.jsonl")) as f:
            req_line, resp_line = [l.strip() for l in f if l.strip()]
        req = decode_request(req_line)
        assert req.trace == {"sid": "s000001", "tid": "t0000002a-000001"}
        assert encode_request(req) == req_line
        resp = decode_response(resp_line)
        assert resp.trace["tid"] == req.trace["tid"]     # same trace
        assert encode_response(resp) == resp_line
        # Live replay: fresh server, same request line, same bytes out.
        srv = LatencyRPCServer(
            _StubService(), obs=Observability(clock=ManualClock(), seed=7),
            auto_start_batcher=False)
        assert srv.handle_line(req_line) == resp_line

    def test_untraced_request_gets_untraced_response(self):
        srv = LatencyRPCServer(_StubService(), obs=Observability(),
                               auto_start_batcher=False)
        out = srv.handle_line('{"id":"u1","method":"available",'
                              '"params":{},"v":1}')
        assert '"trace"' not in out                      # pre-obs bytes

    def test_bad_trace_field_rejected(self):
        for bad in ('{"id":"x","method":"stats","params":{},"trace":"s","v":1}',
                    '{"id":"x","method":"stats","params":{},'
                    '"trace":{"sid":"s1"},"v":1}'):
            with pytest.raises(RPCError):
                decode_request(bad)


class TestMetricsEndpoint:
    def mk(self):
        return LatencyRPCServer(_StubService(), obs=Observability(),
                                auto_start_batcher=False)

    def test_metrics_snapshot_and_prometheus(self):
        srv = self.mk()
        out = srv._metrics({})
        snap = out["snapshot"]
        assert "rpc_batcher_submitted_total" in snap["counters"]
        assert "server" in snap["collected"]
        text = srv._metrics({"format": "prometheus"})["text"]
        assert "# TYPE rpc_batcher_submitted_total counter" in text
        with pytest.raises(RPCError):
            srv._metrics({"format": "xml"})

    def test_metrics_dumps_included_on_request(self):
        srv = self.mk()
        srv.obs.dump("unit_test", k=1)
        out = srv._metrics({"dumps": True})
        assert len(out["dumps"]) == 1
        validate_dump(out["dumps"][0])
        assert "dumps" not in srv._metrics({})

    def test_health_summary_gated_on_explicit_obs(self):
        quiet = LatencyRPCServer(_StubService(), auto_start_batcher=False)
        assert "metrics" not in quiet._health({})        # golden shape
        srv = self.mk()
        h = srv._health({})
        m = h["metrics"]
        assert set(m) == {"queued", "flush_p50_s", "flush_p99_s",
                          "drift_score", "drift_top"}
        assert m["queued"] == 0 and m["drift_score"] == 0.0
        assert m["drift_top"] is None                    # no cells yet
        assert "autopilot" not in h                      # none attached

    def test_prometheus_export_shape(self):
        reg = MetricsRegistry()
        reg.inc("req_total", 3, k="x")
        reg.histogram("lat", buckets=(1.0, 2.0))
        reg.observe("lat", 1.5)
        text = to_prometheus(reg.snapshot(include_collected=False))
        assert 'req_total{k="x"} 3' in text
        assert 'lat_bucket{le="+Inf"} 1' in text
        assert "lat_count 1" in text


# ---------------------------------------------------------------------------
# Conservation under a 32-thread socket flood
# ---------------------------------------------------------------------------

class TestFloodConservation:
    THREADS, PER = 32, 4

    def test_every_request_accounted(self, served):
        obs = Observability()
        svc = LatencyService(served["hub"], default_setting=SOURCE,
                             predictor="gbdt", obs=obs)
        server = LatencyRPCServer(
            svc, obs=obs,
            policy=BatchPolicy(max_batch=8, max_wait_ticks=5,
                               max_queue=1024))
        host, port = server.start()
        n = self.THREADS * self.PER
        graphs = graphs_for(range(1000, 1000 + n))
        errs = []

        def worker(t):
            try:
                with LatencyClient(host, port, timeout=30.0) as c:
                    for i in range(self.PER):
                        c.predict_e2e(graphs[t * self.PER + i])
                    assert c.obs.registry.total("rpc_client_requests_total") \
                        == self.PER
                    assert c.retries == 0
            except Exception as exc:            # surfaced after join
                errs.append(exc)

        threads = [threading.Thread(target=worker, args=(t,))
                   for t in range(self.THREADS)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        assert not errs, errs
        try:
            with LatencyClient(host, port, timeout=30.0) as probe:
                snap = probe.metrics()["snapshot"]
        finally:
            server.stop()

        c = snap["counters"]
        submitted = sum(c["rpc_batcher_submitted_total"].values())
        answered = sum(c["rpc_batcher_answered_total"].values())
        shorts = sum(c.get("rpc_batcher_short_circuits_total", {}).values())
        batched = sum(c.get("rpc_batcher_batched_requests_total",
                            {}).values())
        batches = sum(c.get("rpc_batcher_batches_total", {}).values())
        assert submitted == n                    # nothing lost on admission
        assert answered == n                     # nothing lost on completion
        assert sum(c.get("rpc_batcher_failed_total", {}).values()) == 0
        assert sum(c.get("rpc_batcher_rejected_total", {}).values()) == 0
        assert batched + shorts == n             # flushed + short-circuited
        hists = snap["histograms"]["rpc_batcher_flush_batch_size"]
        hist = next(iter(hists.values()))
        assert hist["count"] == batches          # one size sample per flush
        assert hist["sum"] == batched            # sizes sum to requests
        # Flush durations: one sample per non-wedged flush.
        dur = next(iter(
            snap["histograms"]["rpc_batcher_flush_duration"].values()))
        assert dur["count"] == batches
        # Backend attribution covers every service-side run.
        per_backend = sum(c.get("rpc_flush_backend_total", {}).values())
        service_runs = sum(
            c.get("service_backend_runs_total", {}).values())
        assert per_backend == service_runs > 0
        # Server saw every line (flood + the probe's metrics call).
        assert snap["collected"]["server"]["requests"] == n + 1
        assert snap["collected"]["server"]["errors"] == 0


# ---------------------------------------------------------------------------
# Deterministic replay: same seed, bit-identical snapshot and span tree
# ---------------------------------------------------------------------------

class TestDeterministicReplay:
    def run_once(self):
        # The process-wide feature cache is part of the replayed state:
        # `service.featurize` records how many graphs it featurized.
        clear_graph_feature_cache()
        store, hub, svc0 = build_serving(seed=3)
        clock = ManualClock()
        obs = Observability(clock=clock, seed=13)
        svc = LatencyService(hub, default_setting=SOURCE, predictor="gbdt",
                             obs=obs)
        b = MicroBatcher(svc, BatchPolicy(max_batch=4, max_wait_ticks=2,
                                          max_queue=64),
                         clock=clock, auto_start=False, obs=obs)
        futs = [b.submit(g) for g in graphs_for(range(500, 510))]
        while b.queued():
            if not b.run_pending():
                clock.advance(1)
        for f in futs:
            f.result(0)
        b.close()
        return obs.snapshot_json(), obs.tracer.export()

    def test_two_runs_bit_identical(self):
        snap1, spans1 = self.run_once()
        snap2, spans2 = self.run_once()
        assert snap1 == snap2                    # byte-equal snapshots
        assert spans1 == spans2                  # identical span trees
        assert any(s["name"] == "rpc.batcher.flush" for s in spans1)
        assert any(s["name"] == "service.predict_batch" for s in spans1)
        # Service spans parent under the flush that ran them.
        by_id = {s["sid"]: s for s in spans1}
        svc_spans = [s for s in spans1 if s["name"] == "service.predict_batch"]
        assert svc_spans
        for s in svc_spans:
            assert by_id[s["parent"]]["name"] == "rpc.batcher.flush"


# ---------------------------------------------------------------------------
# Flight-recorder smoke: wedged flushes must leave a usable dump
# ---------------------------------------------------------------------------

class TestFlightRecorderSmoke:
    def test_flight_recorder_wedged_flush_dump(self, served):
        """Under a 100% wedge storm every flush attempt requeues — and
        each one must leave a non-empty, schema-valid dump behind
        (the CI chaos profile runs this with RPC_CHAOS_ITERS=10)."""
        plan = FaultPlan(1, [FaultSpec(site="flush", kind="wedge",
                                       rate=1.0)])
        clock = ManualClock()
        obs = Observability(clock=clock, seed=2)
        b = MicroBatcher(served["service"],
                         BatchPolicy(max_batch=4, max_wait_ticks=1,
                                     max_queue=256),
                         clock=clock, auto_start=False, chaos=plan, obs=obs)
        n = max(4, min(ITERS, 64))
        for g in graphs_for(range(700, 700 + n)):
            b.submit(g)
        assert b.run_pending() == 0              # everything wedged
        assert b.wedged_flushes > 0
        d = obs.recorder.last_dump()
        assert d is not None and d["reason"] == "wedged_flush"
        validate_dump(d)
        assert d["spans"], "dump carries the pre-fault span ring"
        assert any(s["name"] == "rpc.batcher.flush" and s["status"] == "error"
                   for s in d["spans"])
        assert obs.registry.total("obs_flight_dumps_total",
                                  reason="wedged_flush") == b.wedged_flushes
        b.close()

    def test_deadline_timeout_dumps(self, served):
        clock = ManualClock()
        obs = Observability(clock=clock, seed=4)
        b = MicroBatcher(served["service"],
                         BatchPolicy(max_batch=64, max_wait_ticks=100,
                                     max_queue=64),
                         clock=clock, auto_start=False, obs=obs)
        fut = b.submit(graphs_for([801])[0])
        with pytest.raises(RPCError):
            fut.result(0.01)                     # nothing will flush it
        d = obs.recorder.last_dump()
        assert d is not None and d["reason"] == "deadline_timeout"
        validate_dump(d)
        b.close()
