"""Hybrid sparse live-state tests (DESIGN.md SS5).

The load-bearing properties:
  1. Training on the hybrid state (packed-ELL D + HybridW) through the
     fused pipeline is BIT-EXACT vs the dense reference trainer on the
     planted synthetic corpus — topics, D, W, and colsum — for both
     phase-2 routings (dense exact reference and the Pallas kernel).
  2. The overflow policy: capacities are row-nnz upper bounds, so the
     runtime overflow tripwire stays 0; a pinned d_capacity below the
     bound fails at build time with an actionable ValueError.
  3. dense <-> hybrid conversions round-trip exactly, and the measured
     live-state nbytes() beats dense on a Zipf corpus at large K.
  4. The O(L) tail sampler (tail_sampler="sparse") keeps the packed counts
     exactly consistent with the topics and still converges (it draws from
     the same distribution, not the same bits — the documented trade).
  5. Checkpoints stay format-agnostic: topics+rng payloads restore into
     either layout.
"""

import subprocess
import sys
import textwrap

import numpy as np
import pytest

import jax

from repro.core import esca
from repro.lda.model import HybridLayout, LDAConfig
from repro.lda.trainer import LDATrainer

jax.config.update("jax_platform_name", "cpu")


def _reference_trajectory(corpus, cfg, n_iters):
    tr = LDATrainer(corpus, cfg, _from_engine=True)
    state = tr.init_state()
    traj = []
    for _ in range(n_iters):
        state, _ = tr.step(state)
        traj.append((np.asarray(state.topics), np.asarray(state.D),
                     np.asarray(state.W)))
    return traj


# ---------------------------------------------------------------------------
# 1. bit-exactness vs the dense reference trainer
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("impl", ["xla", "pallas"])
def test_hybrid_fused_matches_dense_reference_bitwise(small_corpus, impl):
    traj = _reference_trajectory(
        small_corpus, LDAConfig(n_topics=16, tile_size=512,
                                sampler="three_branch"), 5)
    tr = LDATrainer(small_corpus, LDAConfig(
        n_topics=16, tile_size=512, sampler="three_branch",
        format="hybrid", impl=impl), _from_engine=True)
    pipe = tr.fused_pipeline()
    hs = pipe.from_lda_state(tr.init_state())
    for i, (t_ref, d_ref, w_ref) in enumerate(traj):
        hs, stats, n_surv = pipe.step(hs)
        dense = pipe.to_lda_state(hs)
        assert np.array_equal(np.asarray(hs.topics), t_ref), (impl, i)
        assert np.array_equal(np.asarray(dense.D), d_ref), (impl, i)
        assert np.array_equal(np.asarray(dense.W), w_ref), (impl, i)
        assert np.array_equal(np.asarray(hs.colsum), w_ref.sum(axis=0))
        assert int(hs.overflow) == 0, (impl, i)
        assert 0 < int(n_surv) <= pipe.n_tokens


def test_hybrid_run_fused_scan_equals_stepwise(small_corpus):
    cfg = LDAConfig(n_topics=16, tile_size=512, format="hybrid")
    tr = LDATrainer(small_corpus, cfg, _from_engine=True)
    pipe = tr.fused_pipeline()
    hs_scan, stats, n_surv = pipe.run_fused(
        pipe.from_lda_state(tr.init_state()), 5)
    assert np.asarray(n_surv).shape == (5,)
    hs_step = pipe.from_lda_state(tr.init_state())
    for _ in range(5):
        hs_step, _, _ = pipe.step(hs_step)
    assert np.array_equal(np.asarray(hs_scan.topics),
                          np.asarray(hs_step.topics))
    d_scan, d_step = pipe.to_lda_state(hs_scan), pipe.to_lda_state(hs_step)
    assert np.array_equal(np.asarray(d_scan.D), np.asarray(d_step.D))
    assert np.array_equal(np.asarray(d_scan.W), np.asarray(d_step.W))


def test_trainer_run_hybrid_end_to_end(small_corpus):
    """config.format='hybrid' routes run() through the hybrid pipeline and
    matches the dense reference run bitwise; LLPT still rises."""
    tr_ref = LDATrainer(small_corpus, LDAConfig(
        n_topics=16, tile_size=512, eval_every=5), _from_engine=True)
    s_ref = tr_ref.init_state()
    for _ in range(10):
        s_ref, _ = tr_ref.step(s_ref)

    tr_h = LDATrainer(small_corpus, LDAConfig(
        n_topics=16, tile_size=512, eval_every=5, format="hybrid"), _from_engine=True)
    s_h, hist = tr_h.run(10)
    assert np.array_equal(np.asarray(s_h.topics), np.asarray(s_ref.topics))
    assert np.array_equal(np.asarray(s_h.D), np.asarray(s_ref.D))
    assert np.array_equal(np.asarray(s_h.W), np.asarray(s_ref.W))
    assert len(hist["llpt"]) >= 2
    assert hist["llpt"][-1] > hist["llpt"][0] - 0.05


# ---------------------------------------------------------------------------
# 2. overflow policy
# ---------------------------------------------------------------------------

def test_pinned_d_capacity_below_bound_raises(small_corpus):
    cfg = LDAConfig(n_topics=16, tile_size=512, format="hybrid",
                    d_capacity=2)
    with pytest.raises(ValueError, match="d_capacity"):
        LDATrainer(small_corpus, cfg, _from_engine=True).fused_pipeline()


def test_unrelabeled_corpus_raises():
    from repro.lda.corpus import synthetic_lda_corpus
    c = synthetic_lda_corpus(3, n_docs=30, n_words=50, n_topics=4,
                             mean_doc_len=30)
    # deliberately NOT relabeled; hybrid needs the frequency layout
    with pytest.raises(ValueError, match="relabel"):
        HybridLayout.build(c, LDAConfig(n_topics=8, format="hybrid"))


def test_format_knob_validation(small_corpus):
    with pytest.raises(ValueError, match="format"):
        LDATrainer(small_corpus, LDAConfig(n_topics=8, format="csr"), _from_engine=True)
    with pytest.raises(ValueError, match="tail_sampler"):
        LDATrainer(small_corpus, LDAConfig(n_topics=8,
                                           tail_sampler="magic"), _from_engine=True)


# ---------------------------------------------------------------------------
# 3. conversions + measured memory
# ---------------------------------------------------------------------------

def test_conversion_roundtrip(small_corpus):
    cfg = LDAConfig(n_topics=16, tile_size=512, format="hybrid")
    tr = LDATrainer(small_corpus, cfg, _from_engine=True)
    pipe = tr.fused_pipeline()
    state = tr.init_state()
    back = pipe.to_lda_state(pipe.from_lda_state(state))
    assert np.array_equal(np.asarray(back.topics), np.asarray(state.topics))
    assert np.array_equal(np.asarray(back.D), np.asarray(state.D))
    assert np.array_equal(np.asarray(back.W), np.asarray(state.W))


def test_hybrid_live_state_smaller_than_dense_on_zipf(skewed_corpus):
    """The Table-I direction on MEASURED buffers, not byte models."""
    k = 64
    cfg = LDAConfig(n_topics=k, tile_size=512, format="hybrid")
    tr = LDATrainer(skewed_corpus, cfg, _from_engine=True)
    state = tr.init_state()
    hybrid_bytes = tr.live_state_nbytes(state)
    dense_bytes = state.nbytes()
    assert hybrid_bytes < dense_bytes, (hybrid_bytes, dense_bytes)


# ---------------------------------------------------------------------------
# 4. the O(L) tail sampler
# ---------------------------------------------------------------------------

def test_sparse_tail_sampler_counts_consistent_and_converges(small_corpus):
    tr = LDATrainer(small_corpus, LDAConfig(
        n_topics=16, tile_size=512, format="hybrid",
        tail_sampler="sparse", eval_every=5), _from_engine=True)
    state, hist = tr.run(15)
    D_o, W_o = esca.update_counts(
        tr.word_ids, tr.doc_ids, state.topics, tr.mask,
        n_docs=tr.n_docs, n_words=tr.n_words, n_topics=16)
    assert np.array_equal(np.asarray(state.D), np.asarray(D_o))
    assert np.array_equal(np.asarray(state.W), np.asarray(W_o))
    assert hist["llpt"][-1] > hist["llpt"][0]


# ---------------------------------------------------------------------------
# 5. format-agnostic checkpoints
# ---------------------------------------------------------------------------

def test_checkpoint_payload_restores_into_either_format(small_corpus):
    cfg_h = LDAConfig(n_topics=16, tile_size=512, format="hybrid")
    tr_h = LDATrainer(small_corpus, cfg_h, _from_engine=True)
    pipe = tr_h.fused_pipeline()
    hs = pipe.from_lda_state(tr_h.init_state())
    for _ in range(3):
        hs, _, _ = pipe.step(hs)
    payload = hs.host_payload()
    assert set(payload) == {"topics", "key", "iteration"}  # still topics+rng

    # dense trainer restores and rebuilds dense counts
    tr_d = LDATrainer(small_corpus, LDAConfig(n_topics=16, tile_size=512), _from_engine=True)
    s_d = tr_d.state_from_payload(payload)
    ref = pipe.to_lda_state(hs)
    assert np.array_equal(np.asarray(s_d.D), np.asarray(ref.D))
    assert np.array_equal(np.asarray(s_d.W), np.asarray(ref.W))

    # hybrid trainer restores the same payload back into packed form
    s_h2 = pipe.from_lda_state(tr_h.state_from_payload(payload))
    assert np.array_equal(np.asarray(pipe.to_lda_state(s_h2).D),
                          np.asarray(ref.D))
    assert int(s_h2.iteration) == int(hs.iteration)


# ---------------------------------------------------------------------------
# 6. distributed hybrid (forged devices, subprocess like test_distributed)
# ---------------------------------------------------------------------------

@pytest.mark.slow
def test_dist_hybrid_matches_dist_dense_bitwise():
    """The packed and the dense distributed state run one chain on the
    per-shard draw. The dense side is streamed: its resident data-parallel
    path draws the single backend's topics instead (tests/
    test_distributed.py)."""
    proc = subprocess.run([sys.executable, "-c", textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        import sys; sys.path.insert(0, "src")
        import numpy as np, jax
        from repro.lda.corpus import synthetic_lda_corpus, relabel_by_frequency
        from repro.lda.model import LDAConfig
        from repro.lda.distributed import DistLDATrainer
        from repro.runtime.compat import make_mesh

        corpus = synthetic_lda_corpus(0, n_docs=80, n_words=100, n_topics=8,
                                      mean_doc_len=50)
        corpus, _ = relabel_by_frequency(corpus)
        mesh = make_mesh((4, 1), ("data", "model"))
        trd = DistLDATrainer(corpus, LDAConfig(n_topics=16, tile_size=512,
                                               corpus_residency="streamed",
                                               stream_shards=3),
                             mesh, pad_multiple=256, _from_engine=True)
        trh = DistLDATrainer(corpus, LDAConfig(n_topics=16, tile_size=512,
                                               format="hybrid"),
                             mesh, pad_multiple=256, _from_engine=True)
        sd, sh = trd.init_state(), trh.init_state()
        n_loc = trd.stream.n_loc
        for i in range(5):
            sd, _ = trd.run_fused(sd, 1)
            sh, _ = trh.step(sh)
            assert np.array_equal(sd.host_topics[:, :n_loc],
                                  np.asarray(sh.topics)), i
        Dd, Wd = trd.gather_global(sd)
        Dh, Wh = trh.gather_global(sh)
        assert np.array_equal(Dd, Dh) and np.array_equal(Wd, Wh)
        assert Dh.sum() == corpus.n_tokens == Wh.sum()
        assert int(sh.overflow) == 0          # packed tripwire stayed clean
        assert trh.state_nbytes(sh) < trd.state_nbytes(sd)
        s2, _ = trh.run_fused(trh.init_state(), 5)
        assert np.array_equal(np.asarray(s2.topics), np.asarray(sh.topics))
        # hybrid needs model axis 1
        try:
            DistLDATrainer(corpus, LDAConfig(n_topics=16, format="hybrid"),
                           make_mesh((2, 2), ("data", "model")), _from_engine=True)
            raise SystemExit("expected ValueError")
        except ValueError:
            pass
        print("OK")
    """)], capture_output=True, text=True, timeout=900, cwd=".")
    assert proc.returncode == 0 and "OK" in proc.stdout, proc.stderr[-4000:]
