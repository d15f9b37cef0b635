"""`chip_smoke.py` off the chip: it refuses a host without a TPU, and
its four-chip phase runs on virtual CPU devices at a reduced row count."""
import json
import os
import subprocess
import sys
import textwrap

ROOT = os.path.join(os.path.dirname(__file__), "..")


def _run(args, env_extra=None, timeout=300):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **(env_extra or {}))
    return subprocess.run([sys.executable, *args], capture_output=True,
                          text=True, timeout=timeout, env=env, cwd=ROOT)


def test_refuses_without_tpu_and_prints_no_result():
    for args in ([], ["--chips", "4"]):
        proc = _run([os.path.join(ROOT, "chip_smoke.py"), *args])
        assert proc.returncode != 0
        assert '"ok"' not in proc.stdout
        assert "needs" in proc.stderr


def test_sharded_phase_matches_numpy_on_four_cpu_devices(tmp_path):
    code = textwrap.dedent(f"""
        import json, sys
        sys.path.insert(0, {os.path.abspath(ROOT)!r})
        import chip_smoke as cs
        cs.OUT_DIR = {str(tmp_path)!r}
        with cs.CompileMeter() as meter:
            out = cs.run_sharded(cs.Phases(meter), resolution=32,
                                 min_rows=8192)
        print(json.dumps(out))
    """)
    proc = _run(["-c", code], {
        "XLA_FLAGS": "--xla_force_host_platform_device_count=4"})
    assert proc.returncode == 0, proc.stderr[-3000:]
    out = json.loads(proc.stdout.strip().splitlines()[-1])
    assert out["rows"] >= 8192
    assert out["device_residency"]["sharded_banks"] > 0
    assert set(out["backend_runs"]) == {"jax"}
