"""A whole run of a four-chip cell on four virtual CPU devices: the engine
takes the distributed backend, the check reads its sharded counts in
document order, and one count moved in one shard's D shows as a gap; a
cell that asks for fewer chips than JAX sees runs on the first of them.

The run is a child process, since the device count is fixed when JAX
starts. Its draws, and so its LLPT, and its compiles are the program's own
business and are not held to their limits here."""

import json
import os
import shutil
import subprocess
import sys
import textwrap

import pytest

from bench import harness

TINY = {"name": "tiny4", "n_docs": 400, "n_words": 2000, "mean_doc_len": 60,
        "zipf_exponent": 1.1, "n_topics": 32, "alpha": 1.5625, "beta": 0.01,
        "published": {},
        "assumed": {"doc_len_sigma": 0.75, "length_seed": 0,
                    "topics_per_doc": 4, "doc_topic_conc": 0.3,
                    "background_share": 0.2, "warm_start_iteration": 1000}}
CELL = "tiny4.cold"

CHILD = """
import json, pathlib, sys, time
import numpy as np
sys.path[:0] = [{root!r}, {src!r}]
import jax
from bench import harness
from repro.lda import distributed

tree = pathlib.Path({tree!r})

def run():
    return harness.run({cell!r}, 2**31 + 23, 2.0, False,
                       t_start=time.perf_counter(), require_tpu=False,
                       root=tree, bench=tree / "bench")

sound = run()
real = distributed.DistLDATrainer.run_fused

def moved(self, state, n_iters):
    state, stats = real(self, state, n_iters)
    D = np.asarray(state.D).copy()
    k = int(np.argmax(D[1, 0]))
    D[1, 0, k] -= 1
    D[1, 0, (k + 1) % D.shape[2]] += 1
    D = jax.device_put(D, state.D.sharding)
    return type(state)(topics=state.topics, D=D, W=state.W, key=state.key,
                       iteration=state.iteration), stats

distributed.DistLDATrainer.run_fused = moved
fault = run()
distributed.DistLDATrainer.run_fused = real

# a cell that asks for fewer chips than JAX sees runs on the first ones
import dataclasses
cell = harness.load_cell({cell!r}, tree, tree / "bench")
_, corpus = harness.make_corpus(cell, 5)
held = {{}}
for chips in (1, 2):
    engine = harness.make_engine(corpus, dataclasses.replace(cell, chips=chips))
    engine.fit(0)
    harness.check_held_devices(engine, chips)
    held[chips] = [engine.backend_name, sorted(
        {{d.id for leaf in jax.tree.leaves(engine.state)
          if isinstance(leaf, jax.Array) for d in leaf.devices()}})]
print(json.dumps({{"sound": sound, "fault": fault, "held": held}}))
"""


@pytest.fixture(scope="module")
def tree(tmp_path_factory):
    root = tmp_path_factory.mktemp("four")
    bench = root / "bench"
    shutil.copytree(harness.BENCH, bench,
                    ignore=shutil.ignore_patterns("tests", "__pycache__"))
    (bench / "configs" / "tiny4.json").write_text(json.dumps(TINY))
    spec = json.loads((harness.ROOT / "BENCHMARK.json").read_text())
    spec["configs"] = [{"name": "tiny4", "source": "x", "reduced": [],
                        "file": "bench/configs/tiny4.json", "why": "x"}]
    spec["workloads"] = [{"name": CELL, "config": "tiny4", "traffic": "cold",
                          "chips": 4, "why": "x"}]
    cell = json.loads((bench / "cells" / "nytimes-k1k.cold.json").read_text())
    cell["iter_ref_s"] = 1.0
    (bench / "cells" / f"{CELL}.json").write_text(json.dumps(cell))
    (root / "BENCHMARK.json").write_text(json.dumps(spec))
    return root


@pytest.fixture(scope="module")
def runs(tree):
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
    assert "holds devices [0, 1, 2, 3]" in proc.stderr
    return json.loads(proc.stdout.strip().splitlines()[-1])


def test_four_chip_run_reads_every_check(runs):
    r = runs["sound"]
    assert set(r["checks"]) == {"topic_mismatch", "window_mismatch",
                                "count_gap", "llpt_gap",
                                "compiles_in_window"}
    assert r["device"]["count"] == 4
    assert len(r["device"]["memory_peak_bytes_per_chip"]) == 4
    assert r["checks"]["count_gap"]["value"] == 0
    # read, not held to its limit: the LLPTs compared are of two chains
    # for as long as the distributed draws differ from the reference's
    assert 0 <= r["checks"]["llpt_gap"]["value"] < float("inf")


def test_count_moved_in_one_shard_is_a_gap(runs):
    assert runs["fault"]["checks"]["count_gap"]["value"] > 0


def test_fewer_chips_than_jax_sees_take_the_first(runs):
    assert runs["held"] == {"1": ["single", [0]],
                            "2": ["distributed", [0, 1]]}
