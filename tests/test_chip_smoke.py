"""chip_smoke.py on the CPU: the contract the chip check relies on.

The smoke's parent stays off JAX and drives one server child over HTTP;
here the child is held to one CPU device (the one-device default server,
``Executor`` under ``ClusterExecutor`` — conftest's 8 virtual devices
would make it the mesh one)."""

import json
import os
import re
import shutil
import subprocess
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SMOKE = os.path.join(ROOT, "chip_smoke.py")


def _env(**extra):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **extra)
    env["XLA_FLAGS"] = re.sub(
        r"--xla_force_host_platform_device_count=\d+", "",
        env.get("XLA_FLAGS", "")).strip()
    return env


def test_smoke_passes_on_cpu_and_keeps_the_cache_where_told(tmp_path):
    cache = tmp_path / "cache"
    proc = subprocess.run(
        [sys.executable, SMOKE, "--expect-platform", "cpu", "--shards", "4"],
        env=_env(JAX_COMPILATION_CACHE_DIR=str(cache)),
        capture_output=True, text=True, timeout=600,
    )
    assert proc.returncode == 0, proc.stderr[-4000:]
    lines = proc.stdout.splitlines()
    # the last line is the verdict with exactly these keys; the report
    # is the line before it
    assert json.loads(lines[-1]) == {
        "ok": True,
        "device": {"platform": "cpu", "kind": "cpu", "count": 1}}
    assert len(lines) == 2
    out = json.loads(lines[0])
    assert out["report"] == "chip_smoke"
    assert out["executor"] == "single-device"
    assert out["shards"] == 4
    for key in ("versions", "host_layers", "toolchain", "compile_cache",
                "residency", "seconds", "info"):
        assert key in out, key
    assert {"jax", "jaxlib", "libtpu"} <= set(out["versions"])
    assert set(out["host_layers"]) == {"native", "wire"}
    assert "warm_count_intersect_rtt_ms_median" in out["info"]
    assert {"load", "first_answer_cold",
            "first_answer_after_restart"} <= set(out["seconds"])
    # the cache is where the environment said: a process has one
    # directory, and the child's is not the checkout's (which the other
    # workers of a run compile into, so its listing says nothing here)
    assert out["compile_cache"]["dir"] == str(cache)
    entries = out["compile_cache"]["entries_run1"]
    assert entries > 0
    assert out["compile_cache"]["entries_run2"] == entries
    # the second generation's burst may add a micro-batch size after the
    # smoke's own count, so the directory holds at least that many
    assert sum(1 for f in os.listdir(cache) if f.endswith("-cache")) >= entries


def test_smoke_refuses_the_cpu_unless_told(tmp_path):
    t0 = time.monotonic()
    proc = subprocess.run(
        [sys.executable, SMOKE],
        env=_env(JAX_COMPILATION_CACHE_DIR=str(tmp_path / "cache")),
        capture_output=True, text=True, timeout=120,
    )
    assert proc.returncode != 0
    assert time.monotonic() - t0 < 30  # before any server start or load
    assert proc.stdout == ""  # no result line
    assert "JAX_PLATFORMS" in proc.stderr


def test_smoke_alone_in_a_directory_fails(tmp_path):
    """The chip check also runs the script without the program: that
    must fail, not print a result."""
    shutil.copy(SMOKE, tmp_path / "chip_smoke.py")
    env = _env()
    env.pop("PYTHONPATH", None)
    proc = subprocess.run(
        [sys.executable, "chip_smoke.py", "--expect-platform", "cpu",
         "--shards", "4"],
        cwd=tmp_path, env=env, capture_output=True, text=True, timeout=120,
    )
    assert proc.returncode != 0
    assert proc.stdout == ""
