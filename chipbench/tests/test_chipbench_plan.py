"""The plan cell on the CPU at a short window: the DeepSeek-V2-Lite
configuration goes through set-up, `drive_plan`, the comparison and
``correct``; a planted fault in its reference table reads not correct;
a recorded run holds the planner's spans and counters, which the two
planner readers read; both new cells report ``search_cands_per_s``;
and the configuration file holds the values the program registers."""
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

from chipbench import bank, compare, drive_plan, harness, spec  # noqa: E402
from chipbench import reference_mla_moe  # noqa: E402
from chipbench.reference import ReferenceBank  # noqa: E402

PEAK = {"flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
CELL = "plan.deepseek_v2_lite_ep4"
READERS = ("planner.lower_share_pct.plan", "planner.lower_us_per_op.plan")


@pytest.fixture(scope="module")
def setup(tmp_path_factory):
    bench = spec.benchmark()
    cfg = spec.config(bench, spec.cell(bench, CELL)["config"])
    hub, _ = bank.train_hub(cfg, str(tmp_path_factory.mktemp("hub")))
    return {"cfg": cfg, "hub": hub,
            "tspec": spec.traffic(spec.cell(bench, CELL)["traffic"]),
            "ref": ReferenceBank.load(bank.bank_file(hub.root))}


def _run(setup, workdir, tspec=None, features=None):
    import jax

    out = harness.execute(setup["cfg"], tspec or setup["tspec"],
                          seed=2**31 + 9, seconds=0.2, trace=False,
                          t_start=0.0, devices=jax.devices(),
                          workdir=str(workdir), peak=PEAK, hub=setup["hub"])
    numbers = compare.readings(setup["ref"],
                               features or spec.reference_features(
                                   setup["cfg"]),
                               out["answers"], unanswered=out["run"].failed)
    return out, numbers, compare.judge(numbers, setup["cfg"]["limits"])


def test_plan_run_is_correct_and_its_control_is_not(setup, tmp_path):
    out, numbers, ok = _run(setup, tmp_path)
    run = out["run"]
    assert ok, numbers
    assert run.kind == "search" and run.cands >= 64
    assert run.cands % 64 == 0 and run.attempted == run.cands
    assert {a for a, _, _ in run.annotations} == {"plan.decision"}
    control = compare.readings(setup["ref"],
                               spec.reference_features(setup["cfg"]),
                               out["answers"], precision="bfloat16",
                               against="control")
    assert not compare.judge(control, setup["cfg"]["limits"]), control


def test_doubled_decode_cache_bytes_in_the_reference_read_not_correct(
        setup, tmp_path):
    """The cell's own start state caches 155k tokens, above the bank's
    highest split on cache bytes, where doubling them moves no row; the
    same traffic with one or two short caches shows the fault."""
    mix = dict(setup["tspec"]["mix"], decodes=[1, 2],
               cache_tokens=[16384, 32768])
    decode = reference_mla_moe.FEATURES["mla_decode"]

    def doubled(op):
        vals = decode(op)
        return [vals[0], 2.0 * vals[1]] + vals[2:]

    table = dict(reference_mla_moe.FEATURES, mla_decode=doubled)
    tspec = dict(setup["tspec"], mix=mix)
    _, numbers, ok = _run(setup, tmp_path, tspec)
    assert ok, numbers
    _, numbers, ok = _run(setup, tmp_path, tspec, features=table)
    assert not ok, numbers


def test_a_recorded_run_holds_the_planner_spans_and_counters(setup):
    from repro.obs import Observability

    obs = Observability(tracing=True, span_capacity=harness.SPAN_CAPACITY)
    marks = {}

    def on_window(opening):
        marks[opening] = obs.registry.snapshot(include_collected=False)

    run, _ = drive_plan.drive(setup["cfg"], setup["tspec"], hub=setup["hub"],
                              obs=obs, seed=3, seconds=0.2,
                              on_window=on_window)
    run.reg_before, run.reg_after = marks[True], marks[False]
    run.spans = obs.tracer.export()
    decides = run.spans_named("planner.decide")
    lowers = run.spans_named("planner.lower")
    assert decides and len(lowers) == len(decides)
    assert all(s["attrs"]["candidates"] == 64 for s in decides)
    by_id = {s["sid"]: s for s in run.spans}
    for s in run.spans_named("service.predict_batch"):
        assert by_id[s["parent"]]["name"] == "planner.score"
    ops = run.counter("lm.ops_lowered")
    assert ops == sum(s["attrs"]["ops"] for s in lowers)
    assert run.counter("lm.graphs_lowered") == 64 * len(decides)
    share, per_op = (spec.reader(m)(run) for m in READERS)
    lower_s = sum(run.clipped(s) for s in lowers)
    assert share == pytest.approx(100.0 * lower_s / run.window_s)
    assert per_op == pytest.approx(1e6 * lower_s / ops)
    assert 0 < share < 100 and per_op > 0


@pytest.mark.parametrize("metric", READERS)
def test_planner_readers_are_none_without_planner_spans(metric):
    from chipbench.record import Run

    run = Run(kind="search", cell="", chips=1, setup_s=0.0, t0=0.0, t1=1.0)
    run.spans = [{"name": "service.predict_batch", "sid": "s1",
                  "parent": None, "start": 0.1, "end": 0.2, "attrs": {}}]
    assert spec.reader(metric)(run) is None


@pytest.mark.parametrize("cell", [CELL, "search4.paper_nas_224"])
def test_new_cells_report_search_cands_per_s(cell):
    bench = spec.benchmark()
    names = {m["name"] for m in spec.cell_metrics(bench, cell, "end_to_end")}
    assert names == {"search_cands_per_s", "setup_s"}
    layer = {m["name"] for m in spec.cell_metrics(bench, cell, "per_layer")}
    assert {"tree_fused_roofline", "step_mfu.search",
            "device.idle_pct.search"} <= layer
    assert (set(READERS) <= layer) is (cell == CELL)


def test_every_configuration_keeps_its_digest():
    bench = spec.benchmark()
    for c in bench["configs"]:
        cfg = spec.config(bench, c["name"])
        assert bank.traffic_digest(cfg) == cfg["traffic_digest"]


def test_the_expert_share_is_a_quarter_of_the_published_experts():
    cfg = spec.config(spec.benchmark(), "deepseek_v2_lite_ep4")
    assert cfg["experts_held"] * cfg["expert_parallel"] \
        == cfg["n_routed_experts"]


def test_the_configuration_file_holds_the_registered_published_values():
    # The program registers the catalog's values (PUBLISHED); the cell's
    # graphs are built from its configuration file: they must not drift.
    from dataclasses import replace

    from chipbench import graphs_mla_moe
    from repro.configs import get_arch
    from repro.configs.deepseek_v2_lite import PUBLISHED

    cfg = spec.config(spec.benchmark(), "deepseek_v2_lite_ep4")
    assert {k: cfg[k] for k in PUBLISHED} == PUBLISHED
    assert graphs_mla_moe.arch(cfg) == replace(get_arch("deepseek-v2-lite"),
                                               name=cfg["name"])
