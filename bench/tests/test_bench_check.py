"""The check that decides ``correct``, driven through a whole run at a
size the CPU holds: sound runs pass it; the control and every fault a
training cell can have fail it. The look for a chip is skipped; the rest
of the run is the benchmark's own."""

import json
import shutil
import time

import jax
import jax.numpy as jnp
import pytest

from bench import harness, reference

TINY = {"name": "tiny", "n_docs": 300, "n_words": 2000, "mean_doc_len": 60,
        "zipf_exponent": 1.1, "n_topics": 32, "alpha": 1.5625, "beta": 0.01,
        "published": {},
        "assumed": {"doc_len_sigma": 0.75, "length_seed": 0,
                    "topics_per_doc": 4, "doc_topic_conc": 0.3,
                    "background_share": 0.2, "warm_start_iteration": 1000}}


@pytest.fixture(scope="module")
def tree(tmp_path_factory):
    """A copy of the benchmark with one tiny configuration, held to the
    limits of the real cells."""
    root = tmp_path_factory.mktemp("tiny")
    bench = root / "bench"
    shutil.copytree(harness.BENCH, bench,
                    ignore=shutil.ignore_patterns("tests", "__pycache__"))
    (bench / "configs" / "tiny.json").write_text(json.dumps(TINY))
    spec = json.loads((harness.ROOT / "BENCHMARK.json").read_text())
    spec["configs"] = [{"name": "tiny", "source": "x", "reduced": [],
                        "file": "bench/configs/tiny.json", "why": "x"}]
    spec["workloads"] = []
    for real in ("nytimes-k1k.cold", "pubmed-k1k.warm"):
        traffic = real.split(".")[1]
        name = f"tiny.{traffic}"
        spec["workloads"].append({"name": name, "config": "tiny",
                                  "traffic": traffic, "chips": 1, "why": "x"})
        cell = json.loads((bench / "cells" / f"{real}.json").read_text())
        cell["iter_ref_s"] = 1.0
        (bench / "cells" / f"{name}.json").write_text(json.dumps(cell))
    for m in spec["per_layer"]:
        m["workloads"] = [w["name"] for w in spec["workloads"]]
    (root / "BENCHMARK.json").write_text(json.dumps(spec))
    return root


def _run(tree, cell, seed=2**31 + 11):
    return harness.run(cell, seed, 3.0, False, t_start=time.perf_counter(),
                       require_tpu=False, root=tree, bench=tree / "bench")


@pytest.mark.parametrize("cell", ["tiny.cold", "tiny.warm"])
def test_sound_run_is_correct(tree, cell):
    r = _run(tree, cell)
    assert r["correct"], r["checks"]
    assert list(r)[-1] == "checks"
    assert set(r["metrics"]) == {"train_tokens_per_s", "setup_s"}
    assert r["attempted"] == 3 and r["failed"] == 0
    assert r["checks"]["compiles_in_window"]["value"] == 0


def _wrap_sample(monkeypatch, change):
    from repro.core import three_branch
    real = three_branch.sample

    def faulty(key, plan, word_ids, doc_ids, old_topics, D, W, config):
        new, st = real(key, plan, word_ids, doc_ids, old_topics, D, W,
                       config)
        return change(new, old_topics, config), st

    monkeypatch.setattr(three_branch, "sample", faulty)


def _control(key, plan, word_ids, doc_ids, old_topics, D, W, config):
    """The reference in bfloat16, in the sampler's place."""
    from repro.core import three_branch
    u = jax.random.uniform(key, word_ids.shape, dtype=jnp.float32)
    draws = reference.draw(u, word_ids, doc_ids, D, W, jnp.sum(W, axis=0),
                           alpha=config.alpha_, beta=config.beta,
                           dtype=jnp.bfloat16)
    zero = jnp.float32(0)
    return draws[:, 0], three_branch.ThreeBranchStats(zero, zero, zero, zero)


def _unchanged(monkeypatch):
    from repro.lda import trainer

    def step(self, state):
        return state._replace(iteration=state.iteration + 1), \
            {"frac_skipped": 0.0}

    monkeypatch.setattr(trainer.LDATrainer, "step", step)


FAULTS = {
    "control_bfloat16": lambda mp: mp.setattr(
        __import__("repro.core.three_branch", fromlist=["x"]), "sample",
        _control),
    "state_unchanged": _unchanged,
    "half_unsampled": lambda mp: _wrap_sample(mp, lambda new, old, c: jnp.where(
        jnp.arange(new.shape[0]) < new.shape[0] // 2, old, new)),
    "token_altered": lambda mp: _wrap_sample(mp, lambda new, old, c: jnp.where(
        jnp.arange(new.shape[0]) % 64 == 0, (new + 1) % c.n_topics, new)),
}


def _in_window(monkeypatch, plant):
    """``plant`` the fault as the measured window starts: set-up's checked
    iteration stays sound, the window's own call is broken."""
    real = harness.measure

    def measure(*args, **kw):
        plant(monkeypatch)
        return real(*args, **kw)

    monkeypatch.setattr(harness, "measure", measure)


@pytest.mark.parametrize("phase, caught", [("run", "topic_mismatch"),
                                           ("window", "window_mismatch")])
@pytest.mark.parametrize("fault", sorted(FAULTS))
@pytest.mark.parametrize("cell", ["tiny.cold", "tiny.warm"])
def test_fault_is_not_correct(tree, cell, fault, phase, caught, monkeypatch):
    if phase == "run":
        FAULTS[fault](monkeypatch)
    else:
        _in_window(monkeypatch, FAULTS[fault])
    r = _run(tree, cell)
    assert not r["correct"], r["checks"]
    failed = [k for k, c in r["checks"].items() if c["value"] > c["limit"]]
    assert caught in failed


def test_stale_counts_inside_a_call_are_caught(tree, monkeypatch):
    """Every iteration of a ``fit`` call after its first samples with the
    counts the call started from: each call's first iteration is sound,
    the counts match the topics, and only the window's own call shows it."""
    from repro.lda import trainer
    real_run, real_step = (trainer.LDATrainer._run_stepwise,
                           trainer.LDATrainer.step)
    start = {}

    def run_stepwise(self, state, *args):
        start["state"] = state
        return real_run(self, state, *args)

    def step(self, state):
        s0 = start["state"]
        return real_step(self, state._replace(D=s0.D, W=s0.W))

    monkeypatch.setattr(trainer.LDATrainer, "_run_stepwise", run_stepwise)
    monkeypatch.setattr(trainer.LDATrainer, "step", step)
    r = _run(tree, "tiny.cold")
    assert not r["correct"]
    failed = [k for k, c in r["checks"].items() if c["value"] > c["limit"]]
    assert failed == ["window_mismatch"], r["checks"]


def test_count_update_fault_is_caught(tree, monkeypatch):
    """A count update that drops one token: the draws agree, the counts
    do not."""
    from repro.core import esca
    real = esca.update_counts

    def lossy(word_ids, doc_ids, topics, mask, **kw):
        return real(word_ids, doc_ids, topics, mask.at[0].set(0), **kw)

    monkeypatch.setattr(esca, "update_counts", lossy)
    r = _run(tree, "tiny.cold")
    assert not r["correct"]
    assert r["checks"]["count_gap"]["value"] > 0
