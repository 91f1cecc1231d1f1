"""chip_smoke.py and the start-up contract it rests on, checked on the CPU.

Everything runs in subprocesses: what is under test is process-level
state (which backend a process opens, where its compile cache lives), and
the pytest process has long since initialized JAX.
"""

import json
import os
import subprocess
import sys

import pytest


REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SMOKE = os.path.join(REPO, "chip_smoke.py")


def _run(args, tmp_path, **env_overrides):
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    # a throwaway home: nothing may appear under ~/.cache/hyperspace_tpu
    env["HOME"] = str(tmp_path / "home")
    env.pop("XDG_CACHE_HOME", None)
    os.makedirs(env["HOME"], exist_ok=True)
    for key, value in env_overrides.items():
        if value is None:
            env.pop(key, None)
        else:
            env[key] = value
    return subprocess.run(
        [sys.executable, *args],
        env=env,
        cwd=str(tmp_path),
        capture_output=True,
        text=True,
        timeout=600,
    )


def _cache_dir_in_effect(tmp_path, **env_overrides) -> str:
    code = (
        "import numpy as np, jax, jax.numpy as jnp\n"
        "from hyperspace_tpu.ops import hash as h\n"
        "h._bucket_ids_words(jnp.zeros((2, 8), dtype=np.uint32), 8, 42)\n"
        "print(jax.config.jax_compilation_cache_dir)\n"
    )
    proc = _run(["-c", code], tmp_path, **env_overrides)
    assert proc.returncode == 0, proc.stderr[-2000:]
    return proc.stdout.strip().splitlines()[-1]


def test_refuses_to_run_without_a_chip(tmp_path):
    proc = _run([SMOKE], tmp_path)
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""  # no result line, pass or otherwise
    assert "no TPU" in proc.stderr


@pytest.mark.skipif(
    os.environ.get("HS_NATIVE") == "0",
    reason="the smoke refuses the numpy twins by design",
)
def test_cpu_rehearsal_runs_every_phase(tmp_path):
    proc = _run([SMOKE, "--cpu-rehearsal", "--rows", "6000"], tmp_path)
    assert proc.returncode == 0, proc.stderr[-4000:]
    assert "CPU REHEARSAL" in proc.stderr
    lines = proc.stdout.strip().splitlines()
    assert len(lines) == 2  # the report, then the verdict
    out, verdict = json.loads(lines[0]), json.loads(lines[1])
    # the last line holds the verdict and nothing else; a rehearsal is
    # never the pass line
    assert set(verdict) == {"ok", "device"}
    assert set(verdict["device"]) == {"platform", "kind", "count"}
    assert verdict["ok"] is False and verdict["device"] == out["device"]
    assert isinstance(verdict["device"]["count"], int)
    assert out["ok"] is False and "rehearsal" in out
    assert out["device"]["platform"] == "cpu"
    assert out["claim"] is None
    assert set("abcde") <= set(out["phases"])
    assert out["thresholds"]["source"] == "calibrated"
    assert all(
        out["frontend"][k] == 0
        for k in ("failed", "retries", "degraded", "degraded_pins")
    )
    # the differential and reference checks ran (each answer is recorded
    # only after both held), and the compiled programs were witnessed
    for name in ("join", "join_hybrid", "join_refreshed", "zorder_range",
                 "bloom_point", "device_filter"):
        assert out["answers"][name][0] > 0, name
    assert out["answers"]["join_hybrid"] == out["answers"]["join_refreshed"]
    assert {"ops.zorder._interleave", "ops.bloom._bit_indices"} <= set(
        out["phases"]["d"]["device_programs"]
    )
    assert {
        "ops.filter._run",
        "ops.sort.lexsort_indices",
        "ops.hash._bucket_ids_words",
        "ops.aggregate._seg_sum_count",
    } <= set(out["phases"]["e"]["device_programs"])
    if out["device"]["count"] > 1:
        assert out["mesh"]["exchange_strategy"] == "host"  # CPU mesh
        assert "ops.join._sharded_join" in out["phases"]["c"]["device_programs"]
    else:
        assert "ops.join._jit_vmapped" in out["phases"]["e"]["device_programs"]


def test_compile_cache_is_exactly_the_env_directory(tmp_path):
    want = str(tmp_path / "placed" / "cache")
    got = _cache_dir_in_effect(tmp_path, JAX_COMPILATION_CACHE_DIR=want)
    assert got == want
    assert not os.path.exists(tmp_path / "home" / ".cache" / "hyperspace_tpu")


def test_compile_cache_defaults_to_a_fixed_path_in_the_checkout(tmp_path):
    got = _cache_dir_in_effect(tmp_path, JAX_COMPILATION_CACHE_DIR=None)
    assert got == os.path.join(REPO, ".jax_cache")
    assert not os.path.exists(tmp_path / "home" / ".cache" / "hyperspace_tpu")


def test_importing_the_engine_opens_no_backend(tmp_path):
    code = (
        "import hyperspace_tpu.execution.executor\n"
        "import hyperspace_tpu.session\n"
        "import jax._src.xla_bridge as xb\n"
        "assert not xb._backends, xb._backends\n"
    )
    proc = _run(["-c", code], tmp_path)
    assert proc.returncode == 0, proc.stderr[-2000:]


def test_session_start_runs_nothing_on_the_device(tmp_path):
    """The session's warm thread builds the native kernels and nothing
    else: creating a session opens no JAX backend (the calibration
    probe's device programs run on the first dispatching thread)."""
    code = (
        "import threading\n"
        "from hyperspace_tpu.session import HyperspaceSession\n"
        "HyperspaceSession()\n"
        "for t in threading.enumerate():\n"
        "    if t.name == 'hs-native-warm':\n"
        "        t.join(120)\n"
        "        assert not t.is_alive()\n"
        "import jax._src.xla_bridge as xb\n"
        "assert not xb._backends, xb._backends\n"
    )
    proc = _run(["-c", code], tmp_path)
    assert proc.returncode == 0, proc.stderr[-2000:]
