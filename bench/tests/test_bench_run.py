"""``run.py`` fails, and prints no result, wherever it cannot measure."""

import json
import os
import shutil
import subprocess
import sys

from bench import harness

ARGS = ["--workload", "nytimes-k1k.cold", "--seed", str(2**31 + 5),
        "--seconds", "10", "--trace", "0"]


def _run(cwd, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.update(env_extra or {})
    return subprocess.run([sys.executable, "bench/run.py", *ARGS], cwd=cwd,
                          env=env, capture_output=True, text=True,
                          timeout=300)


def _no_result(proc):
    lines = proc.stdout.strip().splitlines()
    if not lines:
        return True
    try:
        json.loads(lines[-1])
    except ValueError:
        return True
    return False


def test_no_tpu_exits_nonzero():
    proc = _run(harness.ROOT)
    assert proc.returncode != 0
    assert _no_result(proc)
    assert "no TPU" in proc.stderr


def test_benchmark_files_alone_exit_nonzero(tmp_path):
    """A directory with only BENCHMARK.json and the benchmark's files has
    no program to measure."""
    shutil.copy(harness.ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(harness.BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    proc = _run(tmp_path, {"PYTHONPATH": ""})
    assert proc.returncode != 0
    assert _no_result(proc)
