"""Spans of the scoring path, the traced-program counter, and the XLA
module name of the fused traversal.

One fused-tier `predict_batch` records ``service.fingerprint`` and then
``service.predict_batch``, whose children are ``service.featurize``, a
``service.kernel`` per op type (each fused one with its ``tree.stage``,
``tree.dispatch`` and ``tree.wait``) and ``service.assemble``; one
`SearchEngine.step` records ``evolution.step`` and its phases.  The
device-trace readers of the benchmark match the traversal's module by
its name, ``jit__fused_core``, on one device and under ``shard_map``.
"""
import json
import os
import subprocess
import sys
import textwrap
import time

import numpy as np
import pytest

from repro.core.dataset import synthetic_graphs
from repro.core.features import clear_graph_feature_cache
from repro.core.nas_space import NASSpaceConfig, sample_architecture
from repro.core.profiler import DeviceSetting
from repro.kernels import tree_gather
from repro.obs import Observability, Tracer
from repro.pipeline import LatencyService, PredictorHub, ProfileStore
from repro.search import DeviceBudget, SearchConfig, SearchEngine
from repro.transfer import CostModelProfileSession

SOURCE = DeviceSetting("cpu_f32", "float32", "op_by_op")
SPACE = NASSpaceConfig(resolution=16)
REPO_SRC = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "src")

SERVICE_CHILDREN = ("service.featurize", "service.kernel", "service.assemble")
TREE_SPANS = ("tree.stage", "tree.dispatch", "tree.wait")
PHASES_GEN0 = ["evolution.breed", "evolution.decode", "evolution.score",
               "evolution.quality", "evolution.update"]
PHASES = ["evolution.select"] + PHASES_GEN0


@pytest.fixture(scope="module")
def hub():
    store = ProfileStore()
    session = CostModelProfileSession(store=store, seed=3)
    for g in synthetic_graphs(8, resolution=16):
        session.profile_graph(g, SOURCE)
    h = PredictorHub()
    h.train(store, SOURCE, "gbdt", hparams={"n_stages": 20}, min_samples=3)
    return h


@pytest.fixture(scope="module")
def engine_config():
    return SearchConfig(population_size=8, generations=3, children_per_gen=6,
                        tournament_size=4, seed=5, resolution=16)


def _service(hub, obs, backend="jax"):
    return LatencyService(hub, default_setting=SOURCE, predictor="gbdt",
                          inference_backend=backend, obs=obs)


def _graphs(seeds):
    return [sample_architecture(s, SPACE) for s in seeds]


def _by_name(spans, name):
    return [s for s in spans if s["name"] == name]


def test_fused_predict_batch_span_tree(hub):
    obs = Observability()
    svc = _service(hub, obs)
    graphs = _graphs(range(1200, 1206))
    clear_graph_feature_cache()
    svc.predict_batch(graphs)
    spans = obs.tracer.export()
    (fp,) = _by_name(spans, "service.fingerprint")
    (pb,) = _by_name(spans, "service.predict_batch")
    assert fp["attrs"] == {"graphs": 6, "computed": 6}
    assert fp["end"] <= pb["start"]            # a sibling that ends first
    assert fp["parent"] == pb["parent"] is None
    children = [s for s in spans if s["parent"] == pb["sid"]]
    assert {s["name"] for s in children} == set(SERVICE_CHILDREN)
    assert [s["name"] for s in children if s["name"] != "service.kernel"] \
        == ["service.featurize", "service.assemble"]
    (feat,) = _by_name(children, "service.featurize")
    (asm,) = _by_name(children, "service.assemble")
    assert feat["attrs"] == {"graphs": 6, "computed": 6}
    assert asm["attrs"] == {"reports": 6}
    kernels = _by_name(children, "service.kernel")
    assert kernels and all(feat["end"] <= k["start"] and k["end"] <= asm["start"]
                           for k in kernels)
    bank = hub.get(SOURCE, "gbdt")
    assert sorted(k["attrs"]["op_type"] for k in kernels) == sorted(
        {n.op_type for g in graphs for n in g.nodes} & set(bank.predictors))
    for k in kernels:
        assert set(k["attrs"]) == {"op_type", "backend", "rows", "fused"}
        assert k["attrs"]["backend"] == "jax" and k["attrs"]["fused"] is True
        tree = [s for s in spans if s["parent"] == k["sid"]]
        assert [s["name"] for s in tree] == list(TREE_SPANS)
        assert all(a["end"] <= b["start"] for a, b in zip(tree, tree[1:]))
        assert all(k["start"] <= s["start"] and s["end"] <= k["end"]
                   for s in tree)
        stage = tree[0]
        model = bank.predictors[k["attrs"]["op_type"]]
        rows = k["attrs"]["rows"]
        assert stage["attrs"] == {"rows": rows,
                                  "bytes": 4 * rows * len(model.scaler.mean)}
        assert tree[1]["attrs"] == {"form": "dense"}   # depth <= 4 banks
        assert tree[2]["attrs"] == {}
    # The report cache cleared, the same graphs featurize from the
    # feature cache: nothing is computed again.
    # The same graph objects answer their fingerprints from their memo.
    svc.clear_cache()
    svc.predict_batch(graphs)
    feats = _by_name(obs.tracer.export(), "service.featurize")
    assert feats[-1]["attrs"] == {"graphs": 6, "computed": 0}
    fps = _by_name(obs.tracer.export(), "service.fingerprint")
    assert fps[-1]["attrs"] == {"graphs": 6, "computed": 0}


def test_cached_batch_has_no_children(hub):
    obs = Observability()
    svc = _service(hub, obs)
    graphs = _graphs(range(1210, 1213))
    svc.predict_batch(graphs)
    n = len(obs.tracer.export())
    svc.predict_batch(graphs)
    new = obs.tracer.export()[n:]
    assert [s["name"] for s in new] == ["service.fingerprint",
                                        "service.predict_batch"]
    assert new[1]["attrs"]["fresh"] == 0


def test_stats_report_the_fingerprint_memo(hub):
    """New graph objects equal to scored ones are encoded from the memo:
    every params tuple and tensor a hit, none a miss."""
    obs = Observability()
    svc = _service(hub, obs)
    svc.predict_batch(_graphs(range(1225, 1229)))
    before = svc.stats()["fingerprint_memo"]
    assert set(before) == {"hits", "misses", "size"}
    again = _graphs(range(1225, 1229))
    svc.predict_batch(again)
    after = svc.stats()["fingerprint_memo"]
    assert _by_name(obs.tracer.export(), "service.fingerprint")[-1][
        "attrs"] == {"graphs": 4, "computed": 4}
    direct = sum(any(type(v) is tuple for _, v in n.params)
                 for g in again for n in g.nodes)
    assert after["misses"] - before["misses"] == direct
    assert after["hits"] - before["hits"] == sum(
        len(g.nodes) + len(g.tensors) for g in again) - direct
    assert after["size"] == before["size"] > 0


def test_numpy_tier_adds_no_tree_spans(hub):
    obs = Observability()
    svc = _service(hub, obs, backend="numpy")
    svc.predict_batch(_graphs(range(1215, 1218)))
    names = {s["name"] for s in obs.tracer.export()}
    assert "service.kernel" in names
    assert not names & set(TREE_SPANS)


def test_disabled_tracer_records_nothing(hub, engine_config):
    obs = Observability(tracing=False)
    svc = _service(hub, obs)
    svc.predict_batch(_graphs(range(1220, 1224)))
    eng = SearchEngine(svc, [DeviceBudget(SOURCE, 1.0)], engine_config)
    eng.step()
    eng.step()
    assert obs.tracer.export() == []
    assert obs.recorder.spans() == []


def test_default_clock_is_perf_counter():
    tracer = Tracer()
    assert tracer._now is time.perf_counter
    a = time.perf_counter()
    with tracer.span("x"):
        pass
    b = time.perf_counter()
    (s,) = tracer.export()
    assert a <= s["start"] <= s["end"] <= b


def test_engine_step_span_tree(hub, engine_config):
    obs = Observability()
    svc = _service(hub, obs, backend="numpy")
    eng = SearchEngine(svc, [DeviceBudget(SOURCE, 1.0)], engine_config)
    assert eng.obs is obs                      # the service's bundle
    for _ in range(2):
        eng.step()
    spans = obs.tracer.export()
    steps = _by_name(spans, "evolution.step")
    assert [s["attrs"] for s in steps] == [
        {"gen": g, "produced": st.produced, "new_scored": st.new_scored}
        for g, st in enumerate(eng.stats)]
    assert all(s["parent"] is None for s in steps)
    for step, phases in zip(steps, (PHASES_GEN0, PHASES)):
        kids = [s for s in spans if s["parent"] == step["sid"]]
        assert [s["name"] for s in kids] == phases
        assert all(a["end"] <= b["start"] for a, b in zip(kids, kids[1:]))
        (score,) = _by_name(kids, "evolution.score")
        under = [s["name"] for s in spans if s["parent"] == score["sid"]]
        assert under == ["service.fingerprint", "service.predict_batch"]


def test_engine_obs_defaults(hub, engine_config):
    budgets = [DeviceBudget(SOURCE, 1.0)]
    own = Observability()
    svc = _service(hub, Observability(), backend="numpy")
    assert SearchEngine(svc, budgets, engine_config, obs=own).obs is own

    class Bare:                                 # a service with no bundle
        def __init__(self, inner):
            self.predict_multi = inner.predict_multi

    eng = SearchEngine(Bare(svc), budgets, engine_config)
    assert eng.obs is not svc.obs and not eng.obs.tracer.enabled
    eng.step()
    assert eng.obs.tracer.export() == []


def test_programs_traced_counts_each_new_program_once(hub):
    svc = _service(hub, Observability(tracing=False))
    model = hub.get(SOURCE, "gbdt").predictors["conv2d"]
    width = len(model.scaler.mean)
    before = svc.stats()["device_residency"]["lifetime"]["programs_traced"]
    x = np.abs(np.random.default_rng(0).normal(size=(37, width)))
    model.predict_on_device(x.astype(np.float32))
    model.predict_on_device(x[:20].astype(np.float32))
    model.predict_on_device(x.astype(np.float32))   # cached: not traced
    after = svc.stats()["device_residency"]["lifetime"]["programs_traced"]
    assert after - before == 2
    assert tree_gather.residency_counters()["programs_traced"] == after


_MODULE_NAMES = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    import json
    from functools import partial
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    from repro.kernels import tree_gather as tg

    def args(repl, rows_sharding, rows=4096, trees=8, nodes=120, feats=5):
        s = lambda shape, dt, sh=repl: jax.ShapeDtypeStruct(shape, dt,
                                                            sharding=sh)
        return (s((nodes,), jnp.int32), s((nodes,), jnp.float32),
                s((nodes,), jnp.int32), s((nodes,), jnp.int32),
                s((nodes,), jnp.float32), s((trees,), jnp.int32),
                s((), jnp.float32), s((), jnp.float32),
                s((rows, feats), jnp.float32, rows_sharding))

    def name(lowered):
        return lowered.compiler_ir().operation.attributes["sym_name"].value

    one = jax.sharding.SingleDeviceSharding(jax.devices()[0])
    single = tg._fused.lower(*args(one, one), depth=3, kind="sum")
    mesh = Mesh(np.array(jax.devices()), ("rows",))
    db = tg.DeviceBank()
    db.mesh = mesh
    fn = db._sharded_fn(("fused", 3, "sum"),
                        partial(tg._fused_entry, depth=3, kind="sum"),
                        out_rank2=False)
    sharded = fn.lower(*args(NamedSharding(mesh, P()),
                             NamedSharding(mesh, P("rows", None))))
    print(json.dumps({"single": name(single), "sharded": name(sharded),
                      "devices": len(jax.devices())}))
""")


@pytest.fixture(scope="module")
def module_names():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env["PYTHONPATH"] = REPO_SRC + os.pathsep + env.get("PYTHONPATH", "")
    env.pop("XLA_FLAGS", None)
    proc = subprocess.run([sys.executable, "-c", _MODULE_NAMES],
                          capture_output=True, text=True, timeout=300,
                          env=env)
    assert proc.returncode == 0, proc.stderr[-3000:]
    return json.loads(proc.stdout.strip().splitlines()[-1])


@pytest.mark.parametrize("program", ["single", "sharded"])
def test_fused_program_module_name(module_names, program):
    assert module_names["devices"] == 4
    assert module_names[program] == "jit__fused_core"
