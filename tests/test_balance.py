"""Hierarchical workload balancing tests (paper §V-A, DESIGN.md SS9).

The load-bearing properties:
  1. TilePlan invariants (hypothesis-driven): tiles exactly cover all
     tokens with no overlap or gaps; per-tile (first, last) word-run
     metadata matches the actual tile contents; ``max_tiles_per_word`` is
     the EXACT dissection depth (the old ``ceil(count/tile)+1`` bound
     over-counted) and bounds the brute-force depth of every word.
  2. The tile-scheduled kernels (``sample_fused_tiled``,
     ``sample_sparse_tiled``) are bit-equal to their per-token-gather
     counterparts — same row values in, same bits out.
  3. ``balance="tiles"`` is a pure performance knob end to end: the fused
     pipelines (dense xla/pallas, hybrid exact/sparse tail) produce
     bit-identical topics AND counts with tiling on or off, window
     engaged or cond-fallback.
  4. ``assign_token_shards``: every token assigned exactly once, loads
     balanced within the LPT bound, >threshold words dissected.
  5. ``load_imbalance`` keeps the paper's Fig-15 order on a power-law
     corpus, with token tiles the only scheme near an even load.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from _hyp import given, settings, st
from repro.core import balance
from repro.lda.corpus import from_documents, relabel_by_frequency, zipf_corpus
from repro.lda.model import LDAConfig
from repro.lda.trainer import LDATrainer
from repro.train.lda_step import plan_tile_capacity, plan_window

jax.config.update("jax_platform_name", "cpu")


# ---------------------------------------------------------------------------
# 1. TilePlan invariants
# ---------------------------------------------------------------------------

def _brute_force_depth(word_ids: np.ndarray, tile_size: int) -> int:
    """True dissection depth: tiles touched by any single word's run."""
    if len(word_ids) == 0:
        return 1
    tile_of = np.arange(len(word_ids)) // tile_size
    return max(len(np.unique(tile_of[word_ids == v]))
               for v in np.unique(word_ids))


@settings(max_examples=30, deadline=None)
@given(seed=st.integers(0, 10_000), tile_size=st.integers(1, 300))
def test_tile_plan_invariants(seed, tile_size):
    rng = np.random.default_rng(seed)
    n_words = int(rng.integers(1, 60))
    n = int(rng.integers(1, 2000))
    word_ids = np.sort(rng.integers(0, n_words, n)).astype(np.int32)
    plan = balance.build_tiles_from_word_ids(word_ids, tile_size)
    # exact cover: contiguous [t·ts, min((t+1)·ts, n)) ranges partition T
    assert plan.n_tiles == -(-n // tile_size)
    sizes = [min(tile_size, n - t * tile_size) for t in range(plan.n_tiles)]
    assert sum(sizes) == n and min(sizes) > 0          # no overlap, no gap
    for t in range(plan.n_tiles):
        lo, hi = t * tile_size, t * tile_size + sizes[t]
        seg = word_ids[lo:hi]
        assert plan.tile_first_word[t] == seg[0]       # sorted ⇒ min
        assert plan.tile_last_word[t] == seg[-1]
        assert len(np.unique(seg)) <= plan.max_words_per_tile
    # the dissection depth is EXACT, not just an upper bound
    assert plan.max_tiles_per_word == _brute_force_depth(word_ids, tile_size)


@settings(max_examples=20, deadline=None)
@given(seed=st.integers(0, 10_000))
def test_build_tiles_corpus_matches_generic(seed):
    """Corpus CSR route == generic word-id route, field by field."""
    rng = np.random.default_rng(seed)
    n_words = int(rng.integers(2, 40))
    docs = [rng.integers(0, n_words, rng.integers(1, 50)).tolist()
            for _ in range(int(rng.integers(1, 20)))]
    c = from_documents(docs, n_words)
    ts = int(rng.integers(1, 200))
    a = balance.build_tiles(c, ts)
    b = balance.build_tiles_from_word_ids(c.word_ids, ts)
    assert np.array_equal(a.tile_first_word, b.tile_first_word)
    assert np.array_equal(a.tile_last_word, b.tile_last_word)
    assert (a.n_tiles, a.max_words_per_tile, a.max_tiles_per_word) \
        == (b.n_tiles, b.max_words_per_tile, b.max_tiles_per_word)


def test_tiles_spanned_exact_small_words():
    """The fixed bound: words smaller than one tile span 1-2 tiles by
    alignment, never the old ceil+1 over-count."""
    # word of 3 tokens entirely inside tile 0 → exactly 1
    assert balance.tiles_spanned(np.array([2]), np.array([3]), 8)[0] == 1
    # word of 3 tokens straddling the boundary at 8 → exactly 2
    assert balance.tiles_spanned(np.array([6]), np.array([3]), 8)[0] == 2
    # absent word → 0 tiles
    assert balance.tiles_spanned(np.array([5]), np.array([0]), 8)[0] == 0
    # 16-token word aligned at 0 with tile 8 → exactly 2 (old bound: 3)
    assert balance.tiles_spanned(np.array([0]), np.array([16]), 8)[0] == 2


def test_build_tiles_rejects_unsorted():
    with pytest.raises(ValueError, match="sorted"):
        balance.build_tiles_from_word_ids(np.array([3, 1, 2]), 2)


def test_empty_corpus_tile_plan():
    plan = balance.build_tiles_from_word_ids(np.zeros(0, np.int32), 16)
    assert plan.n_tiles == 0 and plan.max_tiles_per_word == 1


# ---------------------------------------------------------------------------
# 2. tiled kernels == per-token-gather kernels, bit for bit
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("n,K,win", [(100, 64, 16), (300, 130, 64),
                                     (257, 512, 128)])
def test_sample_fused_tiled_bit_equal(n, K, win):
    from repro.kernels.sample_fused import sample_fused, sample_fused_tiled
    rng = np.random.default_rng(n + K)
    V = 200
    w_hat = (rng.random((V, K)) * 0.01).astype(np.float32)
    lo = int(rng.integers(0, V - win))
    word_ids = np.sort(rng.integers(lo, lo + win, n)).astype(np.int32)
    d = (rng.integers(0, 50, (n, K))
         * (rng.random((n, K)) < 0.15)).astype(np.int32)
    u = rng.random(n).astype(np.float32)
    ref = sample_fused(jnp.asarray(u), jnp.asarray(d),
                       jnp.asarray(w_hat[word_ids]), alpha=0.1,
                       interpret=True)
    got = sample_fused_tiled(jnp.asarray(u), jnp.asarray(d),
                             jnp.asarray(w_hat), jnp.asarray(word_ids),
                             jnp.int32(word_ids.min()), alpha=0.1,
                             win_words=win, interpret=True)
    for a, b in zip(ref, got):
        assert np.array_equal(np.asarray(a), np.asarray(b))


def test_sample_sparse_tiled_bit_equal():
    from repro.core.sparse import pack_pairs
    from repro.kernels.sample_sparse import sample_sparse, sample_sparse_tiled
    rng = np.random.default_rng(7)
    n, L, K, V, win = 300, 8, 64, 150, 32
    word_ids = np.sort(rng.integers(40, 40 + win, n)).astype(np.int32)
    idx = np.zeros((n, L), np.int32)
    val = np.zeros((n, L), np.int32)
    for i in range(n):
        nnz = rng.integers(0, L + 1)
        idx[i] = rng.choice(K, L, replace=False)
        val[i, :nnz] = rng.integers(1, 30, nnz)
    packed = pack_pairs(jnp.asarray(idx), jnp.asarray(val))
    k1_w = rng.integers(0, K, V).astype(np.int32)
    a1_w = (rng.random(V) * 0.02).astype(np.float32)
    qp_w = (rng.random(V) * 0.05).astype(np.float32)
    w_at = jnp.asarray((rng.random((n, L)) * 0.01).astype(np.float32))
    b1 = jnp.asarray(rng.integers(0, 20, n).astype(np.float32))
    u = jnp.asarray(rng.random(n).astype(np.float32))
    ref = sample_sparse(u, packed, w_at, jnp.asarray(k1_w[word_ids]),
                        jnp.asarray(a1_w[word_ids]), b1,
                        jnp.asarray(qp_w[word_ids]), alpha=0.2,
                        interpret=True)
    got = sample_sparse_tiled(u, packed, w_at, jnp.asarray(word_ids),
                              jnp.int32(word_ids.min()), jnp.asarray(k1_w),
                              jnp.asarray(a1_w), jnp.asarray(qp_w), b1,
                              alpha=0.2, win_words=win, interpret=True)
    for a, b in zip(ref, got):
        assert np.array_equal(np.asarray(a), np.asarray(b))


# ---------------------------------------------------------------------------
# 3. balance="tiles" is a pure perf knob in the fused pipelines
# ---------------------------------------------------------------------------

def _pipeline_trajectory(corpus, cfg, n_iters=6, force_window=None):
    tr = LDATrainer(corpus, cfg, _from_engine=True)
    pipe = tr.fused_pipeline()
    if force_window is not None:
        # engage the word-window path even on a tiny test vocabulary
        pipe.WINDOW_VOCAB_FRACTION = 1
        pipe.win_words = force_window
    fs = pipe.from_lda_state(tr.init_state())
    for _ in range(n_iters // 2):
        fs, _, _ = pipe.run_fused(fs, 2)       # replans between scans
    st = pipe.to_lda_state(fs)
    return np.asarray(st.topics), np.asarray(st.D), np.asarray(st.W)


@pytest.mark.parametrize("impl", ["xla", "pallas"])
def test_tiled_pipeline_bit_equal_dense(small_corpus, impl):
    base = LDAConfig(n_topics=16, tile_size=512, impl=impl)
    ref = _pipeline_trajectory(small_corpus, base)
    for force in (None, 24):                   # tile-capacity only / +window
        cfg = LDAConfig(n_topics=16, tile_size=512, impl=impl,
                        balance="tiles")
        got = _pipeline_trajectory(small_corpus, cfg, force_window=force)
        for a, b in zip(ref, got):
            assert np.array_equal(a, b), (impl, force)


@pytest.mark.parametrize("tail_sampler", ["exact", "sparse"])
def test_tiled_pipeline_bit_equal_hybrid(small_corpus, tail_sampler):
    base = LDAConfig(n_topics=16, tile_size=512, format="hybrid",
                     tail_sampler=tail_sampler)
    ref = _pipeline_trajectory(small_corpus, base)
    cfg = LDAConfig(n_topics=16, tile_size=512, format="hybrid",
                    tail_sampler=tail_sampler, balance="tiles")
    got = _pipeline_trajectory(small_corpus, cfg, force_window=24)
    for a, b in zip(ref, got):
        assert np.array_equal(a, b), tail_sampler


def test_tiny_window_forces_fallback_still_bit_equal(small_corpus):
    """A window far below every chunk span exercises the cond fallback on
    every chunk — correctness must never depend on the plan."""
    base = LDAConfig(n_topics=16, tile_size=512)
    ref = _pipeline_trajectory(small_corpus, base)
    cfg = LDAConfig(n_topics=16, tile_size=512, balance="tiles")
    got = _pipeline_trajectory(small_corpus, cfg, force_window=2)
    for a, b in zip(ref, got):
        assert np.array_equal(a, b)


def test_balance_knob_validation():
    with pytest.raises(ValueError, match="balance"):
        LDAConfig(n_topics=8, balance="magic")
    assert LDAConfig(n_topics=8, balance="tiles").balance == "tiles"


def test_plan_helpers():
    # window: pow2 bucketing, floored, vocab-clamped
    assert plan_window(100, 10_000) == 128
    assert plan_window(1, 10_000) == 64          # floor
    assert plan_window(9_000, 3_000) == 3_000    # vocab clamp
    # tile capacity: working-set cap at 256 KB / (4·K)
    assert plan_tile_capacity(10 ** 9, 10 ** 9, 64) == 1024
    assert plan_tile_capacity(10 ** 9, 10 ** 9, 256) == 256
    # survivor EMA can shrink tiles below the budget
    assert plan_tile_capacity(2_000, 10 ** 9, 64) <= 1024


# ---------------------------------------------------------------------------
# 4. device-level token-balanced shard assignment
# ---------------------------------------------------------------------------

@settings(max_examples=20, deadline=None)
@given(seed=st.integers(0, 10_000), n_shards=st.integers(1, 12))
def test_assign_token_shards_properties(seed, n_shards):
    rng = np.random.default_rng(seed)
    n_words = int(rng.integers(2, 50))
    docs = [rng.integers(0, n_words, rng.integers(1, 60)).tolist()
            for _ in range(int(rng.integers(1, 30)))]
    c = from_documents(docs, n_words)
    token_shard, loads = balance.assign_token_shards(c, n_shards)
    # every token assigned exactly once; loads consistent
    assert token_shard.shape == (c.n_tokens,)
    assert np.all((token_shard >= 0) & (token_shard < n_shards))
    assert np.array_equal(np.bincount(token_shard, minlength=n_shards),
                          loads)
    # LPT with units ≤ ceil(N/4S): max load ≤ mean + unit ⇒ max/mean small
    if c.n_tokens >= 4 * n_shards:
        unit = -(-c.n_tokens // (4 * n_shards))
        assert loads.max() <= c.n_tokens / n_shards + unit


def test_assign_token_shards_dissects_head_word():
    """A power-law head word larger than any shard's fair share MUST be
    dissected across shards — the case document chunking cannot fix."""
    c = zipf_corpus(3, n_docs=150, n_words=400, exponent=1.7,
                    mean_doc_len=80)
    c, _ = relabel_by_frequency(c)
    head_count = int(c.word_token_counts[0])
    n_shards = 8
    assert head_count > c.n_tokens / n_shards    # head dwarfs a fair share
    token_shard, loads = balance.assign_token_shards(c, n_shards)
    head_shards = np.unique(token_shard[c.word_ids == 0])
    assert len(head_shards) >= 2                 # dissected
    assert loads.max() / loads.mean() <= 1.25    # and balanced


def test_shard_corpus_tiles_metadata(skewed_corpus):
    """shard_corpus(balance="tiles"): shared-doc metadata is consistent."""
    from repro.lda.distributed import shard_corpus
    sc = shard_corpus(skewed_corpus, 4, pad_multiple=64, balance="tiles")
    assert sc.owns is not None
    # every real doc has exactly ONE owner row across shards
    owners = []
    for s in range(4):
        nd = int(sc.docs_per_shard[s])
        owners.extend(sc.doc_map[s][:nd][sc.owns[s][:nd] > 0].tolist())
    assert sorted(owners) == list(range(skewed_corpus.n_docs))
    # token loads balanced (the point of the assignment)
    tps = sc.tokens_per_shard
    assert tps.max() / tps.mean() <= 1.25
    # shared_rows point at rows whose doc_map entry is the shared doc
    n_shared = sc.shared_rows.shape[1]
    for s in range(4):
        for j in range(n_shared):
            row = sc.shared_rows[s, j]
            if row < sc.m_local:
                g = sc.doc_map[s][row]
                # that doc's token slots carry slot j
                tok = (sc.doc_ids[s] == row) & (sc.mask[s] > 0)
                if tok.any():
                    assert np.all(sc.shared_slot[s][tok] == j), (s, j, g)


def test_load_imbalance_fig15_order():
    """Kernel-lane max/mean load on a power-law corpus: each scheme of the
    paper's Fig. 15 at most as imbalanced as the one before it, and token
    tiles within 1.2 of even (256-token tiles, many more than the 80
    lanes, so the round-robin measures scheduling, not quantization)."""
    c, _ = relabel_by_frequency(zipf_corpus(0, n_docs=600, n_words=3000,
                                            exponent=1.5, mean_doc_len=150))
    imb = [balance.load_imbalance(c, s, n_units=80, tile_size=256,
                                  dissect_threshold=10_000)["imbalance"]
           for s in ("block_per_word", "dynamic", "dynamic+dissect",
                     "token_tiles")]
    assert imb == sorted(imb, reverse=True), imb
    assert imb[-1] <= 1.2 < imb[-2], imb
