"""The word-sharded parameter server (repro.lda.ps, DESIGN.md SS15).

Four layers, cheapest first:

  1. OwnerLayout — the contiguous word-range partition is EXACT
     (property-tested: disjoint ranges covering [0, V) for any
     (n_words, n_owners, layout), owner_of/owners_touching agree).
  2. The wire protocol — round-commit SSP clock, staleness gating,
     duplicate-push dedup, lost-push resend, owner kill + journal-replay
     revive. Pure numpy, no jax dispatch.
  3. The engine/API surface — DistConfig validation, backend routing.
  4. Forged 8-device legs (slow/chaos markers, subprocess) — the PR's
     acceptance pins: staleness=0 bitwise-equal to the replicated psum
     path for dense AND hybrid, mid-epoch ps_* checkpoints resuming
     bit-identically (including across w_sync strategies and after an
     injected owner kill), chaos drills leaving trajectories unchanged.
"""

import subprocess
import sys
import textwrap

import numpy as np
import pytest

from repro.lda.ps import (OwnerLayout, ParameterServer, PSClient,
                          PushJournal, StalenessViolation)
from repro.runtime import chaos
from tests._hyp import given, settings, st

# ---------------------------------------------------------------------------
# 1. OwnerLayout: the partition is exact
# ---------------------------------------------------------------------------


def _check_partition(layout: OwnerLayout) -> None:
    starts = layout.starts
    assert starts[0] == 0 and starts[-1] == layout.n_words
    assert all(b >= a for a, b in zip(starts, starts[1:]))
    # ranges are disjoint and cover [0, V): every row has exactly one owner
    covered = np.zeros(layout.n_words, np.int64)
    for o in range(layout.n_owners):
        a, b = layout.range_of(o)
        covered[a:b] += 1
    assert (covered == 1).all()
    for row in range(layout.n_words):
        o = layout.owner_of(row)
        a, b = layout.range_of(o)
        assert a <= row < b


@settings(max_examples=200, deadline=None)
@given(n_words=st.integers(1, 400), n_owners=st.integers(1, 12),
       mass_seed=st.integers(0, 2**31 - 1),
       layout=st.sampled_from(["rows", "mass"]))
def test_owner_partition_exact_property(n_words, n_owners, mass_seed,
                                        layout):
    """For ANY (V, n_owners, layout) the owner ranges partition [0, V)."""
    mass = None
    if layout == "mass":
        mass = np.random.default_rng(mass_seed).zipf(1.8, size=n_words)
    _check_partition(OwnerLayout.build(n_words, n_owners, layout=layout,
                                       row_mass=mass))


def test_owner_partition_exact_seeded():
    """The same invariant without hypothesis (the shim skips the property
    test when hypothesis is absent; this keeps the invariant pinned)."""
    rng = np.random.default_rng(0)
    for n_words, n_owners in [(1, 1), (1, 5), (7, 3), (100, 7), (150, 8),
                              (64, 64), (10, 16)]:
        _check_partition(OwnerLayout.build(n_words, n_owners))
        mass = rng.zipf(1.8, size=n_words)
        _check_partition(OwnerLayout.build(n_words, n_owners,
                                           layout="mass", row_mass=mass))


def test_mass_layout_splits_hot_prefix():
    """Zipf-style mass concentrated in early rows: the mass layout gives
    owner 0 FEWER rows than the uniform split (it holds the hot words)."""
    n_words = 200
    mass = 1.0 / (np.arange(n_words) + 1.0) ** 2
    rows = OwnerLayout.build(n_words, 4, layout="rows")
    massy = OwnerLayout.build(n_words, 4, layout="mass", row_mass=mass)
    assert (massy.starts[1] - massy.starts[0]) \
        < (rows.starts[1] - rows.starts[0])
    _check_partition(massy)


def test_owner_layout_rejects_bad_starts():
    with pytest.raises(ValueError, match="0..n_words"):
        OwnerLayout(n_words=10, starts=(0, 5, 9))
    with pytest.raises(ValueError, match="non-decreasing"):
        OwnerLayout(n_words=10, starts=(0, 7, 5, 10))
    with pytest.raises(ValueError, match="row_mass"):
        OwnerLayout.build(10, 2, layout="mass", row_mass=np.ones(9))


def test_owners_touching_matches_owner_of():
    layout = OwnerLayout.build(100, 7)
    for lo, hi in [(0, 100), (13, 14), (10, 60), (99, 100), (30, 30)]:
        want = sorted({layout.owner_of(r) for r in range(lo, hi)})
        assert layout.owners_touching(lo, hi) == want


# ---------------------------------------------------------------------------
# 2. wire protocol: clock, dedup, journals, recovery (pure numpy)
# ---------------------------------------------------------------------------

V, K = 20, 4


def _server(n_workers=2, n_owners=2, staleness=0, seed=0):
    layout = OwnerLayout.build(V, n_owners)
    srv = ParameterServer(layout, K, n_workers, staleness=staleness)
    W = np.random.default_rng(seed).integers(0, 50, (V, K)).astype(np.int32)
    srv.load_global(W)
    return srv, W


def test_round_commits_only_when_all_workers_finish():
    srv, W = _server()
    a, b = PSClient(srv, 0), PSClient(srv, 1)
    d = np.ones((V, K), np.int32)
    a.push_page(0, V, d)
    a.finish_round()
    # worker 1 still in round 0: nothing committed, pulls see the old rows
    assert srv.committed == 0
    assert np.array_equal(b.pull_page(0, V), W)
    b.push_page(0, V, 2 * d)
    b.finish_round()
    assert srv.committed == 1
    assert np.array_equal(a.pull_page(0, V), W + 3)
    assert np.array_equal(srv.gather_global(), W + 3)


def test_staleness_gate():
    srv, _ = _server(n_workers=2, staleness=1)
    fast, slow = PSClient(srv, 0), PSClient(srv, 1)
    # fast worker finishes rounds 0 and 1 alone; committed stays 0
    for _ in range(2):
        fast.push_page(0, V, np.ones((V, K), np.int32))
        fast.finish_round()
    # clock 1 is within staleness=1 of committed=0; clock 2 is not
    assert srv.can_pull(1) and not srv.can_pull(2)
    assert not fast.can_advance()
    with pytest.raises(StalenessViolation):
        fast.pull_page(0, V)
    with pytest.raises(StalenessViolation):
        srv.pull_colsum(clock=2)
    # the slowest worker is always admissible
    assert slow.can_advance()


def test_staleness_zero_pulls_see_exactly_committed():
    srv, W = _server(staleness=0)
    c0, c1 = PSClient(srv, 0), PSClient(srv, 1)
    c0.push_page(0, 10, np.full((10, K), 3, np.int32))
    # queued, not applied: a same-round pull still sees committed rows
    assert np.array_equal(c0.pull_page(0, 10), W[:10])
    c0.finish_round()
    c1.finish_round()
    assert np.array_equal(c0.pull_page(0, 10), W[:10] + 3)


def test_duplicate_push_acks_without_reapplying():
    srv, W = _server(n_workers=1)
    blk = np.ones((5, K), np.int32)
    assert srv.push_page(0, 0, 7, 0, 5, blk)
    assert srv.push_page(0, 0, 7, 0, 5, blk)    # replay of the same seq
    srv.finish_round(0, 0)
    assert np.array_equal(srv.gather_global()[:5], W[:5] + 1)


def test_colsum_is_exact_int():
    srv, W = _server(n_owners=3)
    assert np.array_equal(srv.pull_colsum(clock=0),
                          W.sum(axis=0).astype(np.int32))


def test_journal_accumulates_per_owner_and_trims():
    layout = OwnerLayout.build(V, 2)
    j = PushJournal(0, layout, K)
    # two pages straddling the owner boundary (V//2) in one round
    j.record(0, 5, 15, np.ones((10, K), np.int32))
    j.record(0, 8, 18, np.ones((10, K), np.int32))
    b0, b1 = j.blocks_for(0, 0), j.blocks_for(0, 1)
    assert b0.shape == (10, K) and b1.shape == (10, K)
    assert int(b0.sum() + b1.sum()) == 2 * 10 * K
    assert j.nbytes() > 0
    j.trim(0)
    assert j.blocks_for(0, 0) is None and j.nbytes() == 0


def test_note_checkpoint_requires_committed_clock():
    srv, _ = _server()
    with pytest.raises(ValueError, match="committed"):
        srv.note_checkpoint(3, journals=())


@pytest.mark.chaos
def test_lost_push_resent_from_journal():
    srv, W = _server(n_workers=1)
    c = PSClient(srv, 0)
    with chaos.active(chaos.FaultPlan(ps_lose_pushes=((0, 0),))):
        c.push_page(0, V, np.ones((V, K), np.int32))   # nack -> resend
        c.finish_round()
    assert np.array_equal(srv.gather_global(), W + 1)
    # journal recorded the push exactly once despite the wire retry
    assert c.journal.next_seq == 1


@pytest.mark.chaos
def test_owner_kill_revive_replays_journals():
    srv, W = _server(n_workers=2, n_owners=2)
    a, b = PSClient(srv, 0), PSClient(srv, 1)
    # round 0 commits normally
    for c in (a, b):
        c.push_page(0, V, np.ones((V, K), np.int32))
        c.finish_round()
    # round 1: worker 0's push is pending (uncommitted) when owner 1 dies
    a.push_page(0, V, np.full((V, K), 5, np.int32))
    srv.kill_owner(1)
    with pytest.raises(RuntimeError, match="dead"):
        b.pull_page(0, V)
    with pytest.raises(RuntimeError, match="dead"):
        b.pull_colsum()
    srv.revive_owner(1, journals=[a.journal, b.journal])
    # committed rounds replayed exactly; pending round re-queued
    assert np.array_equal(srv.gather_global(), W + 2)
    a.finish_round()
    b.finish_round()
    assert np.array_equal(srv.gather_global(), W + 7)


@pytest.mark.chaos
def test_revive_requires_all_journals_and_live_owner_check():
    srv, _ = _server(n_workers=2)
    with pytest.raises(ValueError, match="not dead"):
        srv.revive_owner(0, journals=[None, None])
    srv.kill_owner(0)
    with pytest.raises(ValueError, match="journals"):
        srv.revive_owner(0, journals=[None])


def test_owner_bytes_are_a_fraction_of_global():
    layout = OwnerLayout.build(4096, 8)
    srv = ParameterServer(layout, 64, 4)
    global_bytes = 4096 * 64 * 4
    assert srv.max_owner_nbytes() <= global_bytes / 8 + 64 * 4


# ---------------------------------------------------------------------------
# 3. DistConfig validation + backend routing
# ---------------------------------------------------------------------------


def test_dist_config_validation():
    from repro.lda.model import DistConfig
    with pytest.raises(ValueError, match="w_sync"):
        DistConfig(w_sync="gossip")
    with pytest.raises(ValueError, match="staleness"):
        DistConfig(staleness=-1)
    with pytest.raises(ValueError, match="w_sync='ps'"):
        DistConfig(staleness=2)                 # staleness needs ps
    with pytest.raises(ValueError, match="w_sync='ps'"):
        DistConfig(n_owners=4)                  # owner knobs need ps
    with pytest.raises(ValueError, match="owner_layout"):
        DistConfig(w_sync="ps", owner_layout="hash")
    with pytest.raises(ValueError, match="mesh_shape"):
        DistConfig(mesh_shape=(("data",),))
    DistConfig(w_sync="ps", staleness=3, n_owners=2, owner_layout="mass")


def test_ps_trainer_rejects_incompatible_configs(small_corpus):
    from repro.lda.distributed import PSDistTrainer
    from repro.lda.model import DistConfig, LDAConfig
    from repro.runtime.compat import make_mesh
    mesh = make_mesh((1, 1), ("data", "model"))
    kw = dict(n_topics=8, tile_size=256)
    with pytest.raises(ValueError, match="balance"):
        PSDistTrainer(small_corpus, LDAConfig(
            **kw, dist=DistConfig(w_sync="ps", balance="tiles")),
            mesh, _from_engine=True)
    with pytest.raises(ValueError, match="warp"):
        PSDistTrainer(small_corpus, LDAConfig(
            **kw, sampler="warp", dist=DistConfig(w_sync="ps")),
            mesh, _from_engine=True)


def test_engine_single_backend_rejects_ps(small_corpus):
    from repro.lda.api import LDAEngine
    from repro.lda.model import DistConfig, LDAConfig
    with pytest.raises(ValueError, match="parameter server"):
        LDAEngine(small_corpus, LDAConfig(
            n_topics=8, dist=DistConfig(w_sync="ps")), backend="single")


# ---------------------------------------------------------------------------
# 4. forged 8-device legs: the acceptance pins
# ---------------------------------------------------------------------------

_PRELUDE = """
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import sys; sys.path.insert(0, "src")
import numpy as np, jax
from repro.lda.corpus import synthetic_lda_corpus, relabel_by_frequency
from repro.lda.model import LDAConfig, DistConfig
from repro.lda.distributed import DistLDATrainer, PSDistTrainer
from repro.runtime.compat import make_mesh
from repro.runtime import chaos

K = 16
corpus = synthetic_lda_corpus(3, n_docs=40, n_words=150, n_topics=K,
                              mean_doc_len=75)
corpus, _ = relabel_by_frequency(corpus)
mesh = make_mesh((4, 1), ("data", "model"))

def mk(staleness=0, n_owners=None, fmt="dense"):
    cfg = LDAConfig(n_topics=K, seed=11, format=fmt,
                    dist=DistConfig(w_sync="ps", staleness=staleness,
                                    n_owners=n_owners))
    return PSDistTrainer(corpus, cfg, mesh, pad_multiple=64,
                         _from_engine=True)

def mk_rep(fmt="dense"):
    # the replicated trainer on the per-shard draw the PS shares: its
    # streamed residency (the resident dense data-parallel path draws the
    # single backend's topics instead)
    return DistLDATrainer(corpus, LDAConfig(n_topics=K, seed=11,
                                            format=fmt,
                                            corpus_residency="streamed",
                                            stream_shards=3), mesh,
                          pad_multiple=64, _from_engine=True)
"""


def _run_forged(body: str, timeout: int = 900) -> None:
    proc = subprocess.run(
        [sys.executable, "-c", _PRELUDE + textwrap.dedent(body)],
        capture_output=True, text=True, timeout=timeout, cwd=".")
    assert proc.returncode == 0, proc.stderr[-4000:]
    assert "ALL OK" in proc.stdout, proc.stdout[-2000:]


@pytest.mark.slow
def test_ps_staleness0_bitwise_vs_replicated_forged():
    """staleness=0 PS == replicated psum, bitwise, dense AND hybrid —
    and each owner shard holds a strict fraction of the global W bytes."""
    _run_forged("""
    for fmt in ("dense", "hybrid"):
        rep, pst = mk_rep(fmt), mk(fmt=fmt)
        s_r, _ = rep.run_fused(rep.init_state(), 4)
        s_p, _ = pst.run_fused(pst.init_state(), 4)
        D_r, W_r = rep.gather_global(s_r)
        D_p, W_p = pst.gather_global(s_p)
        assert np.array_equal(np.asarray(W_r), W_p), fmt
        assert np.array_equal(np.asarray(D_r), D_p), fmt
        p_r, p_p = rep.host_payload(s_r), pst.host_payload(s_p)
        assert np.array_equal(p_r["topics_global"], p_p["topics_global"])
        assert p_r["iteration"] == p_p["iteration"] == 4
        pst.selfcheck(s_p)
        owner = s_p.server.max_owner_nbytes()
        glob = np.asarray(W_p).nbytes
        assert owner <= 0.35 * glob, (fmt, owner, glob)
    print("ALL OK")
    """)


@pytest.mark.slow
def test_ps_mid_epoch_payload_and_interchange_forged():
    """Mid-round ps_* checkpoints resume bit-identically; payloads
    interchange across w_sync strategies (PS mid-epoch -> replicated
    restores at the cut and redoes the round to the same trajectory)."""
    _run_forged("""
    t0 = mk()
    s0, _ = t0.run_fused(t0.init_state(), 4)
    refD, refW = t0.gather_global(s0)

    t1 = mk()
    s1, _ = t1.run_fused(t1.init_state(), 2)
    s1 = t1.run_shards(s1, 2)            # 2 sub-shards into round 2
    assert s1.cursors.any()
    pay = t1.host_payload(s1)
    assert "ps_cursors" in pay and pay["iteration"] == 2
    t1b = mk()
    s1b = t1b.state_from_payload(pay)
    s1b, _ = t1b.run_fused(s1b, 2)
    s1, _ = t1.run_fused(s1, 2)
    D, W = t1.gather_global(s1)
    Db, Wb = t1b.gather_global(s1b)
    assert np.array_equal(W, Wb) and np.array_equal(D, Db)
    assert np.array_equal(W, refW) and np.array_equal(D, refD)

    # PS mid-epoch payload -> replicated backend: restores at the cut,
    # redoing the round reproduces the identical trajectory
    rep = mk_rep()
    sr = rep.state_from_payload(pay)
    sr, _ = rep.run_fused(sr, 2)
    Dr, Wr = rep.gather_global(sr)
    assert np.array_equal(np.asarray(Wr), refW)
    assert np.array_equal(np.asarray(Dr), refD)
    # replicated boundary payload -> PS backend
    pr = rep.host_payload(sr)
    t5 = mk()
    s5 = t5.state_from_payload(pr)
    assert s5.iteration == 4
    D5, W5 = t5.gather_global(s5)
    assert np.array_equal(W5, refW) and np.array_equal(D5, refD)
    print("ALL OK")
    """)


@pytest.mark.slow
@pytest.mark.chaos
def test_ps_chaos_drills_forged():
    """Owner kill (snapshot + journal replay), lost pushes (journal
    resend), and a slow-worker clock bias under staleness=2 all leave the
    final counts bitwise-equal to the undisturbed run; a mid-epoch ps_*
    checkpoint restores bit-identically after an injected owner kill."""
    _run_forged("""
    t0 = mk()
    s0, _ = t0.run_fused(t0.init_state(), 4)
    refD, refW = t0.gather_global(s0)

    # owner kill after a checkpoint: revive = snapshot + journal replay
    t3 = mk(n_owners=3)
    s3, _ = t3.run_fused(t3.init_state(), 1)
    _ = t3.host_payload(s3)              # checkpoint: snapshot + trim
    with chaos.active(chaos.FaultPlan(ps_kill_owners=((1, 3),))):
        s3, _ = t3.run_fused(s3, 3)
    D3, W3 = t3.gather_global(s3)
    assert np.array_equal(W3, refW) and np.array_equal(D3, refD)

    # lost pushes: client resends from its journal until acked
    t4 = mk()
    with chaos.active(chaos.FaultPlan(ps_lose_pushes=((2, 1), (0, 3)))):
        s4, _ = t4.run_fused(t4.init_state(), 4)
    D4, W4 = t4.gather_global(s4)
    assert np.array_equal(W4, refW) and np.array_equal(D4, refD)

    # staleness=2 + slow-worker bias: genuinely stale pulls, SSP bound
    # holds, run converges (trajectory may legitimately differ)
    t2 = mk(staleness=2)
    with chaos.active(chaos.FaultPlan(ps_slow_workers={0: 2})):
        s2, _ = t2.run_fused(t2.init_state(), 4)
    assert int(s2.clocks.min()) == 4 and int(s2.clocks.max()) == 4
    t2.selfcheck(s2)

    # mid-epoch checkpoint + owner kill -> restore resumes bit-identically
    t6 = mk(n_owners=3)
    s6, _ = t6.run_fused(t6.init_state(), 2)
    s6 = t6.run_shards(s6, 2)
    pay = t6.host_payload(s6)            # the durable mid-round cut
    with chaos.active(chaos.FaultPlan(ps_kill_owners=((2, 2),))):
        s6, _ = t6.run_fused(s6, 2)      # kill + revive in-run
    t6b = mk(n_owners=3)
    s6b = t6b.state_from_payload(pay)    # restore from the pre-kill cut
    s6b, _ = t6b.run_fused(s6b, 2)
    D6, W6 = t6.gather_global(s6)
    D6b, W6b = t6b.gather_global(s6b)
    assert np.array_equal(W6, W6b) and np.array_equal(D6, D6b)
    assert np.array_equal(W6, refW) and np.array_equal(D6, refD)
    print("ALL OK")
    """)


@pytest.mark.slow
def test_ps_engine_supervised_shardwise_forged():
    """The engine front door: DistConfig(w_sync='ps') routes to the PS
    trainer, shard-wise supervised fit cuts mid-round ps_* checkpoints,
    and the result matches the plain fused engine run bitwise."""
    code = """
    import os, tempfile
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import sys; sys.path.insert(0, "src")
    import numpy as np
    from repro.lda.api import LDAEngine, SupervisePolicy
    from repro.lda.corpus import synthetic_lda_corpus, relabel_by_frequency
    from repro.lda.model import LDAConfig, DistConfig

    corpus = synthetic_lda_corpus(3, n_docs=40, n_words=150, n_topics=8,
                                  mean_doc_len=75)
    corpus, _ = relabel_by_frequency(corpus)
    kw = dict(n_topics=16, tile_size=256, seed=11, eval_every=2,
              dist=DistConfig(w_sync="ps"))
    eng = LDAEngine(corpus, LDAConfig(**kw), pad_multiple=64)
    assert eng.backend_name == "distributed" and eng._backend.is_ps
    eng.fit(4)
    W_ref = eng.export().W
    with tempfile.TemporaryDirectory() as d:
        eng2 = LDAEngine(corpus, LDAConfig(**kw), pad_multiple=64,
                         checkpoint_dir=d)
        eng2.fit(4, supervise=SupervisePolicy(checkpoint_shards=1))
        assert np.array_equal(eng2.export().W, W_ref)
    print("ALL OK")
    """
    proc = subprocess.run(
        [sys.executable, "-c", textwrap.dedent(code)],
        capture_output=True, text=True, timeout=900, cwd=".")
    assert proc.returncode == 0, proc.stderr[-4000:]
    assert "ALL OK" in proc.stdout, proc.stdout[-2000:]
