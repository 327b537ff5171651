"""Fused-iteration pipeline tests (train/lda_step.py).

The load-bearing properties:
  1. The incremental −1/+1 delta update stays EXACTLY equal to the
     esca.update_counts full-rebuild oracle over many iterations, including
     padded/masked tokens (which must never move counts).
  2. fused_step reproduces LDATrainer.step's topics AND D/W counts
     bit-for-bit given the same key — for both phase-2 routings (the dense
     exact reference and the Pallas sample_fused kernel).
  3. run_fused (lax.scan) == repeated fused_step == the trainer loop, and
     chunk capacity is a pure performance knob (any capacity, same bits).
  4. The maintained Ŵ column sum never drifts from W.sum(axis=0).
  5. A scanned stretch is one dispatch: no host sync inside it, on the
     dense, hybrid and warp pipelines.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.core import esca
from repro.lda.model import LDAConfig
from repro.lda.trainer import LDATrainer
from repro.train.lda_step import plan_capacity

jax.config.update("jax_platform_name", "cpu")


# ---------------------------------------------------------------------------
# 1. delta update == full rebuild (property test, no hypothesis needed)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("seed", [0, 1, 7, 42])
def test_delta_update_matches_rebuild_oracle(seed):
    """Random topic trajectories: delta-applied counts == rebuilt counts."""
    rng = np.random.default_rng(seed)
    n, n_docs, n_words, K = 513, 20, 30, 9
    word_ids = jnp.asarray(np.sort(rng.integers(0, n_words, n)), jnp.int32)
    doc_ids = jnp.asarray(rng.integers(0, n_docs, n), jnp.int32)
    # ~10% pad tokens, interleaved to catch masked-token handling
    mask = jnp.asarray((rng.random(n) > 0.1).astype(np.int32))
    topics = jnp.asarray(rng.integers(0, K, n), jnp.int32)
    D, W = esca.update_counts(word_ids, doc_ids, topics, mask,
                              n_docs=n_docs, n_words=n_words, n_topics=K)
    colsum = jnp.sum(W, axis=0, dtype=jnp.int32)
    for it in range(5):
        # partial resample: most tokens keep their topic (the converged
        # regime the delta update is built for); pad tokens get new topics
        # too — they must still not move any count
        keep = rng.random(n) < 0.6
        new = np.where(keep, np.asarray(topics), rng.integers(0, K, n))
        new_topics = jnp.asarray(new, jnp.int32)
        D, W = esca.delta_update_counts(D, W, word_ids, doc_ids, topics,
                                        new_topics, mask)
        colsum = esca.delta_update_colsum(colsum, topics, new_topics, mask)
        topics = new_topics
        D_ref, W_ref = esca.update_counts(word_ids, doc_ids, topics, mask,
                                          n_docs=n_docs, n_words=n_words,
                                          n_topics=K)
        assert np.array_equal(np.asarray(D), np.asarray(D_ref)), it
        assert np.array_equal(np.asarray(W), np.asarray(W_ref)), it
        assert np.array_equal(np.asarray(colsum),
                              np.asarray(W_ref).sum(axis=0)), it


# ---------------------------------------------------------------------------
# 2./3. fused pipeline == reference trainer, bit for bit
# ---------------------------------------------------------------------------

def _reference_trajectory(corpus, cfg, n_iters):
    tr = LDATrainer(corpus, cfg, _from_engine=True)
    state = tr.init_state()
    traj = []
    for _ in range(n_iters):
        state, _ = tr.step(state)
        traj.append((np.asarray(state.topics), np.asarray(state.D),
                     np.asarray(state.W)))
    return tr, traj


@pytest.mark.parametrize("impl", ["xla", "pallas"])
def test_fused_step_matches_trainer_bitwise(small_corpus, impl):
    cfg = LDAConfig(n_topics=16, tile_size=512, sampler="three_branch",
                    impl=impl)
    # reference uses the dense exact path regardless of impl
    _, traj = _reference_trajectory(
        small_corpus, LDAConfig(n_topics=16, tile_size=512,
                                sampler="three_branch"), 5)
    tr = LDATrainer(small_corpus, cfg, _from_engine=True)
    pipe = tr.fused_pipeline()
    fs = pipe.from_lda_state(tr.init_state())
    for i, (t_ref, d_ref, w_ref) in enumerate(traj):
        fs, stats, n_surv = pipe.step(fs)
        assert np.array_equal(np.asarray(fs.topics), t_ref), (impl, i)
        assert np.array_equal(np.asarray(fs.D), d_ref), (impl, i)
        assert np.array_equal(np.asarray(fs.W), w_ref), (impl, i)
        assert np.array_equal(np.asarray(fs.colsum), w_ref.sum(axis=0))
        assert 0 < int(n_surv) <= pipe.n_tokens


def test_run_fused_scan_equals_stepwise(small_corpus):
    cfg = LDAConfig(n_topics=16, tile_size=512, sampler="three_branch")
    tr = LDATrainer(small_corpus, cfg, _from_engine=True)
    pipe = tr.fused_pipeline()
    fs_scan, stats, n_surv = pipe.run_fused(
        pipe.from_lda_state(tr.init_state()), 5)
    assert np.asarray(n_surv).shape == (5,)
    assert np.asarray(stats.frac_skipped).shape == (5,)
    fs_step = pipe.from_lda_state(tr.init_state())
    for _ in range(5):
        fs_step, _, _ = pipe.step(fs_step)
    assert np.array_equal(np.asarray(fs_scan.topics),
                          np.asarray(fs_step.topics))
    assert np.array_equal(np.asarray(fs_scan.D), np.asarray(fs_step.D))
    assert np.array_equal(np.asarray(fs_scan.W), np.asarray(fs_step.W))


def test_capacity_is_a_pure_perf_knob(small_corpus):
    """Any survivor-chunk capacity gives identical bits."""
    cfg = LDAConfig(n_topics=16, tile_size=512, sampler="three_branch")
    outs = []
    for cap in (64, 300, 10 ** 6):
        tr = LDATrainer(small_corpus, LDAConfig(
            n_topics=16, tile_size=512, sampler="three_branch",
            survivor_capacity=cap), _from_engine=True)
        pipe = tr.fused_pipeline()
        fs, _, _ = pipe.run_fused(pipe.from_lda_state(tr.init_state()), 3,
                                  replan=False)
        outs.append(np.asarray(fs.topics))
    assert np.array_equal(outs[0], outs[1])
    assert np.array_equal(outs[1], outs[2])


def test_trainer_run_fused_end_to_end(small_corpus):
    """config.fused routes run() through the pipeline; LLPT still rises and
    the fused history matches the reference run's final state bitwise."""
    cfg = LDAConfig(n_topics=16, tile_size=512, sampler="three_branch",
                    eval_every=5)
    tr_ref = LDATrainer(small_corpus, cfg, _from_engine=True)
    s_ref = tr_ref.init_state()
    for _ in range(10):
        s_ref, _ = tr_ref.step(s_ref)

    tr_f = LDATrainer(small_corpus, LDAConfig(
        n_topics=16, tile_size=512, sampler="three_branch",
        eval_every=5, fused=True), _from_engine=True)
    s_f, hist = tr_f.run(10)
    assert np.array_equal(np.asarray(s_f.topics), np.asarray(s_ref.topics))
    assert np.array_equal(np.asarray(s_f.D), np.asarray(s_ref.D))
    assert len(hist["llpt"]) >= 2
    assert hist["llpt"][-1] > hist["llpt"][0] - 0.05  # converging, not noise


def test_run_fused_resume_hits_absolute_boundaries(small_corpus):
    """A resumed fused run (start iteration not on an eval boundary, odd
    n_iters) must still eval at the same ABSOLUTE iterations as run()."""
    cfg = LDAConfig(n_topics=16, tile_size=512, sampler="three_branch",
                    eval_every=5, fused=True)
    tr = LDATrainer(small_corpus, cfg, _from_engine=True)
    state = tr.init_state()
    for _ in range(3):                       # land on iteration 3
        state, _ = tr.step(state)
    state, hist = tr.run_fused(9, state=state)   # iterations 4..12
    assert int(state.iteration) == 12
    # evals at the absolute boundaries 5 and 10 (plus the first chunk)
    assert 5 in hist["iteration"] and 10 in hist["iteration"]


def test_plan_capacity_buckets():
    assert plan_capacity(0, 10 ** 6) == 2048           # floor
    assert plan_capacity(100_000, 10 ** 6) == 16384    # ~ema/8 -> next pow2
    assert plan_capacity(10 ** 9, 4096) == 4096        # clamped to corpus


# ---------------------------------------------------------------------------
# 5. the corpus arrays reach the compiled scan as arguments
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("extra", [{}, {"format": "hybrid",
                                        "tail_sampler": "sparse"},
                                   {"sampler": "warp"}])
def test_fused_scan_captures_no_corpus_constants(small_corpus, extra):
    """jit embeds a closed-over array in the program as a constant; the
    token arrays (N-long) must be arguments, or every executable carries
    a copy of the corpus."""
    import warnings
    cfg = LDAConfig(n_topics=16, tile_size=512, fused=True, **extra)
    tr = LDATrainer(small_corpus, cfg, _from_engine=True)
    pipe = tr.fused_pipeline()
    fs = pipe.from_lda_state(tr.init_state())
    args = (fs, pipe._token_args())
    if pipe.sampler == "warp":
        args += (pipe._build_proposal(fs),)
    n_bytes = 4 * pipe.n_tokens
    was = jax.config.jax_captured_constants_warn_bytes
    jax.config.update("jax_captured_constants_warn_bytes", n_bytes)
    try:
        with warnings.catch_warnings(record=True) as seen:
            warnings.simplefilter("always")
            pipe._get_fn(1).lower(*args)
    finally:
        jax.config.update("jax_captured_constants_warn_bytes", was)
    assert not [w for w in seen if "constants were captured" in str(w.message)]


# ---------------------------------------------------------------------------
# 6. a scanned stretch makes no host sync
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("extra", [{}, {"format": "hybrid"},
                                   {"sampler": "warp"}])
def test_scanned_stretch_makes_no_host_sync(small_corpus, extra):
    """After one compiling stretch, ``run_fused(replan=False)`` runs under
    ``transfer_guard("disallow")``: a scalar read of a device value
    (``int``, ``float``, ``bool``) or an implicit host-to-device copy
    inside the scanned region raises. On the CPU a whole-array
    ``np.asarray`` is zero-copy and passes the guard."""
    cfg = LDAConfig(n_topics=16, tile_size=512, fused=True, **extra)
    tr = LDATrainer(small_corpus, cfg, _from_engine=True)
    pipe = tr.fused_pipeline()
    fs, _, _ = pipe.run_fused(pipe.from_lda_state(tr.init_state()), 3)
    fs, _, _ = pipe.run_fused(fs, 3, replan=False)          # compiles
    jax.block_until_ready(fs.topics)
    with jax.transfer_guard("disallow"):
        fs, _, _ = pipe.run_fused(fs, 3, replan=False)
        jax.block_until_ready(fs.topics)
