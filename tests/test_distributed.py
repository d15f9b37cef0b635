"""Multi-device distributed tests (subprocess: 8 or 4 host devices).

The main test process sees 1 device (XLA device count locks at first
jax import), so sharding/pjit/pipeline tests run in subprocesses with
XLA_FLAGS=--xla_force_host_platform_device_count=N.
"""
import os
import subprocess
import sys
import textwrap

import pytest

REPO_SRC = os.path.join(os.path.dirname(__file__), "..", "src")


def run_subprocess(body: str, timeout: int = 420, devices: int = 8) -> str:
    code = textwrap.dedent(f"""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count={devices}"
        import jax
        import jax.numpy as jnp
        import numpy as np
    """) + textwrap.dedent(body)
    env = dict(os.environ)
    env["PYTHONPATH"] = REPO_SRC + os.pathsep + env.get("PYTHONPATH", "")
    env.pop("XLA_FLAGS", None)
    proc = subprocess.run([sys.executable, "-c", code], capture_output=True,
                          text=True, timeout=timeout, env=env)
    assert proc.returncode == 0, f"stderr:\n{proc.stderr[-3000:]}"
    return proc.stdout


def test_sharded_train_step_matches_single_device():
    out = run_subprocess("""
        import dataclasses
        from repro.configs import get_arch
        from repro.models import build_model
        from repro.distributed.sharding import shard_params, input_shardings
        from repro.distributed.trainstep import init_train_state, make_train_step
        from repro.launch.mesh import make_mesh

        cfg = get_arch("qwen2-72b").reduced()
        model = build_model(cfg)
        state = init_train_state(model, jax.random.PRNGKey(0))
        batch = {"tokens": jnp.ones((8, 32), jnp.int32),
                 "labels": jnp.ones((8, 32), jnp.int32)}
        step = make_train_step(model)
        # single device reference
        s1, m1 = jax.jit(step)(state, batch)
        # sharded on a (2, 4) mesh
        mesh = make_mesh((2, 4), ("data", "model"))
        with jax.set_mesh(mesh):
            pshard = shard_params(jax.eval_shape(lambda: state.params), mesh)
            s2, m2 = jax.jit(step)(state, batch)
        d = abs(float(m1["loss"]) - float(m2["loss"]))
        assert d < 5e-2, (float(m1["loss"]), float(m2["loss"]))
        print("LOSS_MATCH", float(m1["loss"]), float(m2["loss"]))
    """)
    assert "LOSS_MATCH" in out


def test_fsdp_gather_numerics_match_tp():
    out = run_subprocess("""
        import dataclasses
        from repro.configs import get_arch
        from repro.models import build_model
        from repro.launch.mesh import make_mesh

        base = get_arch("qwen2-72b").reduced()
        mesh = make_mesh((2, 4), ("data", "model"))
        batch = {"tokens": jnp.ones((4, 32), jnp.int32),
                 "labels": jnp.ones((4, 32), jnp.int32)}
        losses = {}
        for fsdp in (False, True):
            cfg = dataclasses.replace(base, fsdp_gather=fsdp, seq_shard=fsdp)
            model = build_model(cfg)
            params = model.init(jax.random.PRNGKey(0))
            with jax.set_mesh(mesh):
                loss, _ = jax.jit(model.loss)(params, batch)
            losses[fsdp] = float(loss)
        assert abs(losses[True] - losses[False]) < 5e-2, losses
        print("FSDP_MATCH", losses)
    """)
    assert "FSDP_MATCH" in out


def test_pipeline_parallel_matches_sequential():
    out = run_subprocess("""
        from functools import partial
        from repro.distributed.pipeline import (
            pipeline_forward, split_layers_to_stages, pipeline_bubble_fraction)
        from repro.launch.mesh import make_mesh

        rng = np.random.default_rng(0)
        L, d = 8, 16
        ws = jnp.asarray(rng.standard_normal((L, d, d)) * 0.1, jnp.float32)

        def layer_fn(w, x):
            return jnp.tanh(x @ w)

        M, mb, s = 4, 2, 4
        x = jnp.asarray(rng.standard_normal((M, mb, s, d)), jnp.float32)

        # sequential reference
        ref = x
        for i in range(L):
            ref = layer_fn(ws[i], ref)

        mesh = make_mesh((4,), ("pipe",))
        stages = split_layers_to_stages(ws, 4)
        out = pipeline_forward(layer_fn, stages, x, mesh=mesh, axis="pipe")
        err = float(jnp.abs(out - ref).max())
        assert err < 1e-5, err
        assert 0 < pipeline_bubble_fraction(4, 4) < 1
        print("PIPELINE_MATCH", err)
    """)
    assert "PIPELINE_MATCH" in out


def test_compressed_psum_under_shard_map():
    out = run_subprocess("""
        from jax.sharding import PartitionSpec as P
        from repro.distributed.compression import compressed_psum
        from repro.launch.mesh import make_mesh

        mesh = make_mesh((8,), ("data",))
        x = jnp.asarray(np.random.default_rng(0).standard_normal((8, 64)),
                        jnp.float32)

        def body(xl):
            return compressed_psum(xl[0], "data")

        f = jax.shard_map(body, mesh=mesh, in_specs=P("data"), out_specs=P())
        got = f(x)
        want = x.sum(0)
        rel = float(jnp.linalg.norm(got - want) / jnp.linalg.norm(want))
        assert rel < 0.02, rel
        print("PSUM_OK", rel)
    """)
    assert "PSUM_OK" in out


def test_elastic_mesh_shapes():
    import numpy as np
    from repro.launch.mesh import elastic_mesh_shape
    assert elastic_mesh_shape(256, model_parallel=16) == ((16, 16), ("data", "model"))
    assert elastic_mesh_shape(192, model_parallel=16) == ((12, 16), ("data", "model"))
    # degraded pod: model axis shrinks to fit
    shape, axes = elastic_mesh_shape(24, model_parallel=16)
    assert int(np.prod(shape)) == 24


def test_elastic_recovery_roundtrip(tmp_path):
    out = run_subprocess(f"""
        from repro.checkpoint import CheckpointManager
        from repro.distributed.elastic import recover
        from repro.distributed.trainstep import init_train_state
        from repro.configs import get_arch
        from repro.models import build_model
        from repro.launch.mesh import make_mesh

        cfg = get_arch("granite-moe-1b-a400m").reduced()
        model = build_model(cfg)
        state = init_train_state(model, jax.random.PRNGKey(0))
        ckpt = CheckpointManager(r"{tmp_path}", async_save=False)
        ckpt.save(42, state, {{"mesh_shape": [8]}})
        # recover onto a DIFFERENT mesh (2x4) — elastic reshard
        mesh = make_mesh((2, 4), ("data", "model"))
        restored, plan = recover(ckpt, state, mesh=mesh)
        assert plan.resumed and plan.step == 42
        a = np.asarray(jax.tree_util.tree_leaves(state.params)[0])
        b = np.asarray(jax.tree_util.tree_leaves(restored.params)[0])
        np.testing.assert_array_equal(a, b)
        print("ELASTIC_OK")
    """)
    assert "ELASTIC_OK" in out


def test_dryrun_reduced_cell_on_8_devices():
    """End-to-end mini dry-run: reduced arch on a small mesh, full record."""
    out = run_subprocess("""
        from repro.launch.dryrun import run_cell
        from repro.launch.mesh import make_mesh
        from repro.configs import get_arch

        mesh = make_mesh((2, 4), ("data", "model"))
        rec = run_cell("granite-moe-1b-a400m", "train_4k", mesh,
                       cfg_override=get_arch("granite-moe-1b-a400m").reduced())
        assert rec["ok"], rec.get("error")
        assert rec["cost"]["flops_per_device"] > 0
        assert rec["memory"]["temp_bytes"] > 0
        print("DRYRUN_OK", rec["collective_bytes"])
    """)
    assert "DRYRUN_OK" in out


@pytest.mark.parametrize("devices", [8, 4])
def test_sharded_tree_flush_matches_numpy_and_is_deterministic(devices):
    """Giant flushes shard rows across the device mesh; reassembly is
    row-order deterministic and bank upload stays at one."""
    out = run_subprocess("""
        from repro.core.predictors import GBDTPredictor

        rng = np.random.default_rng(0)
        x = np.abs(rng.standard_normal((200, 8))) * np.linspace(1, 30, 8)
        y = x @ rng.random(8) + 0.1
        m = GBDTPredictor(n_stages=20).fit(x, y)
        # 2050 rows: above SHARD_MIN_ROWS and not a multiple of 8 or 4
        # forced host devices, so the pad-and-slice path is exercised.
        q = np.abs(rng.standard_normal((2050, 8))) * np.linspace(1, 30, 8)
        flat = m.flat()
        xs = m.scaler.transform(q)
        ref = flat.predict_trees(xs, backend="numpy")
        got = flat.predict_trees(xs, backend="jax")
        db = flat.device_bank()
        assert db.mesh is not None and db.stats()["sharded"]
        assert db.mesh.devices.size == len(jax.devices())
        assert got.shape == ref.shape
        assert np.allclose(got, ref, rtol=1e-4, atol=1e-7)
        again = flat.predict_trees(xs, backend="jax")
        assert np.array_equal(got, again)          # deterministic reassembly
        assert db.uploads == 1                     # bank uploaded once
        # Fused device scoring rides the same sharded staging.
        host = m.predict(q)
        dev = m.predict_on_device(np.asarray(q, np.float32))
        assert np.allclose(dev, host, rtol=1e-3, atol=1e-5)
        # Small flushes stay unsharded (below SHARD_MIN_ROWS).
        small = flat.predict_trees(xs[:64], backend="jax")
        assert np.allclose(small, ref[:64], rtol=1e-4, atol=1e-7)
        print("SHARDED_FLUSH_OK")
    """, devices=devices)
    assert "SHARDED_FLUSH_OK" in out
