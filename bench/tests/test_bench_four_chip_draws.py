"""A whole run of a four-chip cell on four virtual CPU devices, held to
its limits: the data-parallel backend draws the reference's topics at
set-up's checked iteration and at the window's last, and the window
lowers no program."""

import json
import os
import subprocess
import sys
import textwrap

from bench import harness
from bench.tests.test_bench_four_chip import CELL, tree  # noqa: F401

CHILD = """
import json, pathlib, sys, time
sys.path[:0] = [{root!r}, {src!r}]
from bench import harness
tree = pathlib.Path({tree!r})
r = harness.run({cell!r}, 2**31 + 29, 2.0, False, t_start=time.perf_counter(),
                require_tpu=False, root=tree, bench=tree / "bench")
print(json.dumps(r))
"""


def test_four_chip_run_draws_the_reference_topics(tree):  # noqa: F811
    body = CHILD.format(root=str(harness.ROOT),
                        src=str(harness.ROOT / "src"), tree=str(tree),
                        cell=CELL)
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    proc = subprocess.run([sys.executable, "-c", textwrap.dedent(body)],
                          capture_output=True, text=True, timeout=300,
                          env=env, cwd=str(tree))
    assert proc.returncode == 0, proc.stderr[-4000:]
    assert "backend=distributed" in proc.stderr
    r = json.loads(proc.stdout.strip().splitlines()[-1])
    checks = {k: c["value"] for k, c in r["checks"].items()}
    assert checks["topic_mismatch"] == 0.0, checks
    assert checks["window_mismatch"] == 0.0, checks
    assert checks["compiles_in_window"] == 0, checks
    assert checks["count_gap"] == 0, checks
    assert r["correct"], checks
