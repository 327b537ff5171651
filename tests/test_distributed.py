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
    """run_fused (donated state, stacked stats) is bit-identical to calling
    step() the same number of times — the multi-device analogue of
    tests/test_fused_step.py's scan-vs-stepwise pin."""
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


@pytest.mark.parametrize("balance", ["none", "tiles"])
def test_host_counts_match_a_scatter(balance):
    """The host count build (one histogram pass) gives the per-token
    scatter's counts, every holder of a dissected document included."""
    import numpy as np
    from repro.lda.corpus import relabel_by_frequency, synthetic_lda_corpus
    from repro.lda.distributed import _host_counts, shard_corpus
    corpus, _ = relabel_by_frequency(synthetic_lda_corpus(
        0, n_docs=80, n_words=100, n_topics=8, mean_doc_len=50))
    sc = shard_corpus(corpus, 4, pad_multiple=256, balance=balance)
    K = 16
    t = np.random.default_rng(0).integers(0, K, sc.word_ids.shape)
    Dg = np.zeros((corpus.n_docs, K), np.int64)
    W = np.zeros((corpus.n_words, K), np.int32)
    for s in range(sc.n_shards):
        sel = sc.mask[s] > 0
        np.add.at(Dg, (sc.doc_map[s][sc.doc_ids[s][sel]], t[s][sel]), 1)
        np.add.at(W, (sc.word_ids[s][sel], t[s][sel]), 1)
    D_got, W_got = _host_counts(sc, corpus, K, t)
    assert W_got.dtype == np.int32 and np.array_equal(W_got, W)
    for s in range(sc.n_shards):
        nd = int(sc.docs_per_shard[s])
        assert np.array_equal(D_got[s, :nd], Dg[sc.doc_map[s][:nd]])
        assert not D_got[s, nd:].any()


# ---------------------------------------------------------------------------
# the data-parallel path draws the single backend's topics (4 forged devices)
# ---------------------------------------------------------------------------

_PRELUDE_DP = """
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import sys; sys.path.insert(0, "src")
import numpy as np, jax, jax.numpy as jnp
from jax.sharding import PartitionSpec as P
from repro.core import three_branch
from repro.lda.api import LDAEngine
from repro.lda.corpus import synthetic_lda_corpus, relabel_by_frequency
from repro.lda.model import LDAConfig
from repro.runtime.compat import make_mesh

corpus = synthetic_lda_corpus(0, n_docs=120, n_words=200, n_topics=8,
                              mean_doc_len=60)
corpus, _ = relabel_by_frequency(corpus)
# a 64-token tile: survivor chunks of 64 slots on a shard's 2,048
cfg = LDAConfig(n_topics=16, tile_size=64, eval_every=5)

def engines():
    single = LDAEngine(corpus, cfg, backend="single")
    dist = LDAEngine(corpus, cfg, backend="distributed",
                     mesh=make_mesh((4, 1), ("data", "model")),
                     pad_multiple=256)
    assert dist.trainer.draws_as_single
    return single, dist

def document_D(dist):
    tr = dist.trainer
    D_sh = np.asarray(dist.state.D)
    D = np.zeros((corpus.n_docs, cfg.n_topics), D_sh.dtype)
    for s in range(tr.sc.n_shards):
        nd = int(tr.sc.docs_per_shard[s])
        D[tr.sc.doc_map[s][:nd]] = D_sh[s][:nd]
        assert not D_sh[s][nd:].any()           # pad rows hold nothing
    return D
"""


def _run_dp(body: str):
    proc = subprocess.run(
        [sys.executable, "-c", _PRELUDE_DP + textwrap.dedent(body)],
        capture_output=True, text=True, timeout=600, cwd=".")
    assert proc.returncode == 0, proc.stderr[-4000:]
    assert "OK" in proc.stdout, proc.stdout[-2000:]


@pytest.mark.slow
@pytest.mark.parametrize("start", ["random", "converged"])
def test_data_parallel_matches_single_bitwise(start):
    """From one payload, three iterations on a (4, 1) mesh give the single
    backend's topics, D and W bit for bit (and its next key): from random
    topics every shard draws every token, from a converged state every
    shard draws survivor chunks."""
    _run_dp(f"""
    single, dist = engines()
    single.fit(40 if {start!r} == "converged" else 0)
    dist.restore(single.host_payload())
    hs, hd = single.fit(3), dist.fit(3)
    ps, pd = single.host_payload(), dist.host_payload()
    assert np.array_equal(ps["topics_global"], pd["topics_global"])
    assert np.array_equal(ps["key"], pd["key"])
    assert ps["iteration"] == pd["iteration"]
    assert np.array_equal(np.asarray(single.state.D), document_D(dist))
    assert np.array_equal(np.asarray(single.state.W),
                          np.asarray(dist.state.W))
    branch = 1.0 if {start!r} == "converged" else 0.0
    for h in (hs, hd):
        assert [s["phase2_compacted"] for s in h["stats"]] == [branch]
    print("OK")
    """)


@pytest.mark.slow
def test_data_parallel_llpt_matches_single():
    """One seed trains one chain on either backend, and the LLPT the
    distributed fit scores on the chips (per-shard terms, one psum)
    matches the single backend's within 1e-6."""
    _run_dp("""
    single, dist = engines()
    hs, hd = single.fit(6), dist.fit(6)
    assert np.array_equal(single.host_payload()["topics_global"],
                          dist.host_payload()["topics_global"])
    assert hs["iteration"] == hd["iteration"] == [1, 5]
    for a, b in zip(hs["llpt"], hd["llpt"]):
        assert abs(a - b) <= 1e-6 * abs(a), (a, b)
    # dissected documents hold their whole row on every holder, so a
    # token-balanced state is scored on the chips too
    from repro.lda.distributed import _host_llpt
    tiles = LDAEngine(corpus, LDAConfig(n_topics=16, tile_size=64,
                                        balance="tiles"),
                      backend="distributed",
                      mesh=make_mesh((4, 1), ("data", "model")),
                      pad_multiple=256)
    assert tiles.trainer.sc.shared_rows is not None
    tiles.fit(2)
    got = tiles.trainer.llpt(tiles.state)
    want = _host_llpt(corpus, tiles.config,
                      *tiles.trainer.gather_global(tiles.state))
    assert abs(got - want) <= 1e-6 * abs(want), (got, want)
    print("OK")
    """)


@pytest.mark.slow
def test_data_parallel_fit_lowers_once():
    """fit(1) loads every program the data-parallel path runs; a later
    fit(3) lowers none, whatever its length."""
    _run_dp("""
    _, dist = engines()
    first = dist.fit(1)["lowered"]
    for name in ("jit__dp_sample", "jit__dp_update", "jit__shard_llpt"):
        assert name in first, (name, first)
    assert dist.fit(3)["lowered"] == []
    assert dist.fit(7)["lowered"] == []
    print("OK")
    """)


@pytest.mark.slow
@pytest.mark.parametrize("start", ["random", "converged"])
def test_data_parallel_stats_match_single(start):
    """Over three iterations from one payload, the data-parallel
    iteration's stats are the single backend's: each token share counts
    the same tokens, the branch taken is the same, and from random topics
    every token is drawn. From
    a converged state each shard runs its own survivor chunks, so the
    slot share counts the shards' chunks, under the dense branch's."""
    _run_dp(f"""
    # a whole number of tiles: the single backend has no pad slots, so
    # its means over slots are means over the real tokens, as the shards'
    corpus = synthetic_lda_corpus(0, n_docs=108, n_words=200, n_topics=8,
                                  mean_doc_len=60)
    corpus, _ = relabel_by_frequency(corpus)
    assert corpus.n_tokens % cfg.tile_size == 0
    single, dist = engines()
    single.fit(40 if {start!r} == "converged" else 0)
    dist.restore(single.host_payload())
    s_state = single.state
    _, d_stats = dist.trainer.run_fused(dist.state, 3)
    branch = 1.0 if {start!r} == "converged" else 0.0
    for i in range(3):
        s_state, s_st = single.trainer.step(s_state)
        for f in ("frac_skipped", "frac_m_final", "frac_unchanged",
                  "frac_at_max", "phase2_compacted"):
            # as token counts: a share's last bit follows how the
            # platform divides by the token count
            got = round(float(getattr(d_stats, f)[i]) * corpus.n_tokens)
            want = round(float(s_st[f]) * corpus.n_tokens)
            assert got == want, (i, f, got, want)
        assert float(d_stats.phase2_compacted[i]) == branch
        slots = float(d_stats.frac_phase2_slots[i])
        if branch:
            assert 0.0 < slots < three_branch.COMPACT_BELOW, slots
        else:
            assert slots == float(s_st["frac_phase2_slots"]) == 1.0
    print("OK")
    """)


@pytest.mark.slow
def test_shard_uniforms_are_the_global_draw():
    """A shard's uniforms are the single backend's one vector (drawn at
    its padded length) at the shard's global positions."""
    _run_dp("""
    _, dist = engines()
    tr = dist.trainer
    key = jax.random.split(jax.random.PRNGKey(2**31 + 7))[1]
    shard_u = jax.jit(jax.shard_map(
        lambda g: three_branch.uniforms_at(key, g[0])[None], mesh=tr.mesh,
        in_specs=P(tr.data_axes), out_specs=P(tr.data_axes),
        check_vma=False))
    u = np.asarray(shard_u(tr.global_pos))
    n_pad = -(-corpus.n_tokens // cfg.tile_size) * cfg.tile_size
    full = np.asarray(jax.random.uniform(key, (n_pad,), dtype=jnp.float32))
    for s in range(tr.sc.n_shards):
        sel = tr.sc.mask[s] > 0
        assert np.array_equal(u[s][sel], full[tr.sc.global_pos[s][sel]]), s
    print("OK")
    """)
