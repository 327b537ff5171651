"""Multi-device LDA tests. These run in a subprocess so the forged device
count (XLA_FLAGS) never leaks into the rest of the suite."""

import subprocess
import sys
import textwrap

import pytest

_PRELUDE = """
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import sys; sys.path.insert(0, "src")
import numpy as np, jax, jax.numpy as jnp
from repro.lda.corpus import synthetic_lda_corpus, relabel_by_frequency
from repro.lda.model import LDAConfig
from repro.lda.distributed import DistLDATrainer
from repro.core import llpt as llpt_mod

corpus = synthetic_lda_corpus(0, n_docs=80, n_words=100, n_topics=8,
                              mean_doc_len=50)
corpus, _ = relabel_by_frequency(corpus)
cfg = LDAConfig(n_topics=16, tile_size=512)

def global_llpt(tr, state):
    D, W = tr.gather_global(state)
    return float(llpt_mod.llpt(
        jnp.asarray(corpus.word_ids), jnp.asarray(corpus.doc_ids),
        jnp.ones(corpus.n_tokens, jnp.int32), jnp.asarray(D.astype(np.int32)),
        jnp.asarray(W.astype(np.int32)), alpha=cfg.alpha_, beta=cfg.beta))
"""


def _run(body: str):
    proc = subprocess.run(
        [sys.executable, "-c", _PRELUDE + textwrap.dedent(body)],
        capture_output=True, text=True, timeout=900, cwd=".")
    assert proc.returncode == 0, proc.stderr[-4000:]
    return proc.stdout


@pytest.mark.slow
def test_dist_converges_and_conserves_tokens():
    out = _run("""
    mesh = jax.make_mesh((4, 2), ("data", "model"))
    tr = DistLDATrainer(corpus, cfg, mesh, pad_multiple=256, _from_engine=True)
    state = tr.init_state()
    ll0 = global_llpt(tr, state)
    for _ in range(12):
        state, stats = tr.step(state)
        D, W = tr.gather_global(state)
        assert D.sum() == corpus.n_tokens == W.sum()
    ll1 = global_llpt(tr, state)
    assert ll1 > ll0 + 0.1, (ll0, ll1)
    assert 0.0 < float(stats.frac_skipped) < 1.0
    print("OK", ll0, ll1)
    """)
    assert "OK" in out


@pytest.mark.slow
def test_run_fused_matches_stepwise():
    """The scanned run_fused (donated state, stacked stats) is bit-identical
    to calling step() the same number of times — the multi-device analogue
    of tests/test_fused_step.py's scan-vs-stepwise pin."""
    out = _run("""
    mesh = jax.make_mesh((4, 2), ("data", "model"))
    tr = DistLDATrainer(corpus, cfg, mesh, pad_multiple=256, _from_engine=True)
    s_step = tr.init_state()
    for _ in range(4):
        s_step, last_stats = tr.step(s_step)
    s_scan, stats = tr.run_fused(tr.init_state(), 4)
    assert np.array_equal(np.asarray(s_scan.topics), np.asarray(s_step.topics))
    assert np.array_equal(np.asarray(s_scan.D), np.asarray(s_step.D))
    assert np.array_equal(np.asarray(s_scan.W), np.asarray(s_step.W))
    assert int(s_scan.iteration) == 4
    assert np.asarray(stats.frac_skipped).shape == (4,)
    assert float(stats.frac_skipped[-1]) == float(last_stats.frac_skipped)
    D, W = tr.gather_global(s_scan)
    assert D.sum() == corpus.n_tokens == W.sum()
    print("OK")
    """)
    assert "OK" in out


@pytest.mark.slow
def test_multipod_mesh_axes():
    """(pod, data, model) mesh — the multi-pod collective path lowers+runs."""
    out = _run("""
    mesh = jax.make_mesh((2, 2, 2), ("pod", "data", "model"))
    tr = DistLDATrainer(corpus, cfg, mesh, pad_multiple=256, _from_engine=True)
    state = tr.init_state()
    for _ in range(4):
        state, stats = tr.step(state)
    D, W = tr.gather_global(state)
    assert D.sum() == corpus.n_tokens == W.sum()
    print("OK")
    """)
    assert "OK" in out


@pytest.mark.slow
def test_model_axis_parity():
    """Topic-sharded sampling must be distribution-compatible with model=1:
    identical (corpus, seed) runs on (4,1) and (2,2) meshes converge to the
    same LLPT plateau and conserve counts."""
    out = _run("""
    res = {}
    for shape, names in (((4, 1), ("data", "model")),
                         ((2, 2), ("data", "model"))):
        mesh = jax.make_mesh(shape, names)
        tr = DistLDATrainer(corpus, cfg, mesh, pad_multiple=256, _from_engine=True)
        state = tr.init_state()
        for _ in range(15):
            state, _ = tr.step(state)
        res[shape] = global_llpt(tr, state)
    print("RES", res)
    vals = list(res.values())
    assert abs(vals[0] - vals[1]) < 0.15, res
    print("OK")
    """)
    assert "OK" in out


@pytest.mark.slow
def test_elastic_restore_across_mesh_sizes():
    """Checkpoint on a 4-shard mesh, restore on a 2-shard mesh: counts are
    rebuilt for the new chunking and training continues (elastic scaling)."""
    out = _run("""
    mesh4 = jax.make_mesh((4, 2), ("data", "model"))
    tr4 = DistLDATrainer(corpus, cfg, mesh4, pad_multiple=256, _from_engine=True)
    s4 = tr4.init_state()
    for _ in range(5):
        s4, _ = tr4.step(s4)
    payload = tr4.host_payload(s4)
    D4, W4 = tr4.gather_global(s4)

    mesh2 = jax.make_mesh((2, 4), ("data", "model"))
    tr2 = DistLDATrainer(corpus, cfg, mesh2, pad_multiple=256, _from_engine=True)
    s2 = tr2.state_from_payload(payload)
    D2, W2 = tr2.gather_global(s2)
    # same global counts, different layout
    assert np.array_equal(D4, D2) and np.array_equal(W4, W2)
    assert int(s2.iteration) == 5
    before = global_llpt(tr2, s2)
    for _ in range(8):
        s2, _ = tr2.step(s2)
    after = global_llpt(tr2, s2)
    assert after > before - 0.02  # keeps converging (allow plateau noise)
    print("OK", before, after)
    """)
    assert "OK" in out


@pytest.mark.slow
def test_token_balanced_sharding_with_dissection():
    """balance="tiles": token-balanced shard assignment with word
    dissection. Dissected documents keep replicated D rows glued by the
    shared-row delta psum — the gathered global D must stay EXACTLY the
    histogram of the checkpoint topics, and training must converge like
    document chunking does."""
    out = _run("""
    from repro.lda.distributed import shard_corpus
    cfg_t = LDAConfig(n_topics=16, tile_size=512, balance="tiles")
    sc = shard_corpus(corpus, 4, pad_multiple=256, balance="tiles")
    tps = sc.tokens_per_shard
    assert tps.max() / tps.mean() <= 1.25, tps        # token-balanced
    assert sc.shared_rows is not None                 # docs were dissected

    mesh = jax.make_mesh((4, 2), ("data", "model"))
    tr = DistLDATrainer(corpus, cfg_t, mesh, pad_multiple=256, _from_engine=True)
    state = tr.init_state()
    ll0 = global_llpt(tr, state)
    for _ in range(12):
        state, stats = tr.step(state)
        D, W = tr.gather_global(state)
        assert D.sum() == corpus.n_tokens == W.sum()
    # replica consistency: replicated rows must equal the global histogram
    payload = tr.host_payload(state)
    Dref = np.zeros((corpus.n_docs, 16), np.int64)
    np.add.at(Dref, (corpus.doc_ids, payload["topics_global"]), 1)
    D, W = tr.gather_global(state)
    assert np.array_equal(D, Dref), "dissected D replicas drifted"
    ll1 = global_llpt(tr, state)
    assert ll1 > ll0 + 0.1, (ll0, ll1)
    # scanned == stepwise, same as the doc-chunked path
    s_scan, _ = tr.run_fused(tr.init_state(), 4)
    s_step = tr.init_state()
    for _ in range(4):
        s_step, _ = tr.step(s_step)
    assert np.array_equal(np.asarray(s_scan.topics),
                          np.asarray(s_step.topics))
    assert np.array_equal(np.asarray(s_scan.D), np.asarray(s_step.D))
    # elastic restore onto a doc-chunked trainer: same global counts
    tr2 = DistLDATrainer(corpus, cfg, jax.make_mesh((2, 1),
                         ("data", "model")), pad_multiple=256, _from_engine=True)
    s2 = tr2.state_from_payload(payload)
    D2, W2 = tr2.gather_global(s2)
    assert np.array_equal(D2, D) and np.array_equal(W2, W)
    # hybrid + tiles is rejected with an actionable error (on the pure
    # data-parallel mesh hybrid otherwise supports)
    try:
        DistLDATrainer(corpus, LDAConfig(n_topics=16, format="hybrid",
                       balance="tiles"),
                       jax.make_mesh((4, 1), ("data", "model")),
                       pad_multiple=256, _from_engine=True)
        raise AssertionError("hybrid+tiles should be rejected")
    except ValueError as e:
        assert "tiles" in str(e)
    print("OK", ll0, ll1)
    """)
    assert "OK" in out


@pytest.mark.slow
def test_moe_parallel_paths_match_local():
    """a2a-EP (seq-sharded) and ep-policy (batch-sharded) MoE dispatch are
    numerically identical to the single-device path at lossless capacity."""
    proc = subprocess.run([sys.executable, "-c", textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        import sys; sys.path.insert(0, "src")
        import dataclasses, numpy as np, jax, jax.numpy as jnp
        from repro.configs import REGISTRY
        from repro.models.registry import get_model, reduced_config
        from repro.runtime.sharding import LogicalRules, use_rules
        cfg = reduced_config(REGISTRY["deepseek-moe-16b"],
                             capacity_factor=64.0)
        cfg = dataclasses.replace(cfg, param_dtype="float32")
        api = get_model(cfg)
        params = api.init(jax.random.PRNGKey(0))
        rng = np.random.default_rng(0)
        B, S = 8, 32
        batch = {"inputs": jnp.asarray(
                     rng.integers(0, cfg.vocab_size, (B, S)), jnp.int32),
                 "labels": jnp.asarray(
                     rng.integers(0, cfg.vocab_size, (B, S)), jnp.int32),
                 "mask": jnp.ones((B, S), jnp.int32)}
        ref = float(jax.jit(api.loss)(params, batch))
        from repro.runtime.compat import make_mesh
        mesh = make_mesh((2, 4), ("data", "model"))
        for policy in ("tp", "ep"):
            rules = LogicalRules(mesh, policy=policy)
            def f(p, b):
                with use_rules(rules):
                    return api.loss(p, b)
            got = float(jax.jit(f)(params, batch))
            assert abs(got - ref) < 5e-3, (policy, got, ref)
        print("OK")
    """)], capture_output=True, text=True, timeout=900, cwd=".")
    assert proc.returncode == 0 and "OK" in proc.stdout, proc.stderr[-3000:]
