"""Pallas kernel sweeps: every kernel vs its ref.py oracle across
shapes/dtypes (interpret mode; the TPU target compiles the same code)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.sparse import pack_pairs
from repro.kernels import ref
from repro.kernels.histogram import histogram
from repro.kernels.sample_fused import sample_fused
from repro.kernels.sample_sparse import sample_sparse


@pytest.mark.parametrize("n,K", [(64, 32), (100, 64), (300, 130),
                                 (128, 512), (257, 1000), (16, 2048)])
def test_sample_fused_vs_ref(n, K):
    rng = np.random.default_rng(n * 1000 + K)
    d = (rng.integers(0, 50, (n, K)) * (rng.random((n, K)) < 0.1)).astype(np.int32)
    w = rng.random((n, K)).astype(np.float32) * 0.01
    u = rng.random(n).astype(np.float32)
    alpha = 50.0 / K
    t_k, m_k, s_k, q_k = sample_fused(
        jnp.asarray(u), jnp.asarray(d), jnp.asarray(w), alpha=alpha,
        interpret=True)
    t_r, m_r, s_r, q_r = ref.sample_fused_ref(
        jnp.asarray(u), jnp.asarray(d), jnp.asarray(w), alpha=alpha)
    np.testing.assert_allclose(m_k, m_r, rtol=1e-5)
    np.testing.assert_allclose(s_k, s_r, rtol=1e-4, atol=1e-6)
    np.testing.assert_allclose(q_k, q_r, rtol=1e-4, atol=1e-6)
    # float-associativity at CDF boundaries may flip a measure-zero set
    assert np.mean(np.asarray(t_k) != np.asarray(t_r)) < 2e-3


@pytest.mark.parametrize("tile_t,block_k", [(64, 128), (128, 512), (256, 256)])
def test_sample_fused_tiling_invariance(tile_t, block_k):
    """Output must not depend on the BlockSpec tiling (masses to fp tol)."""
    rng = np.random.default_rng(5)
    n, K = 200, 700
    d = (rng.integers(0, 50, (n, K)) * (rng.random((n, K)) < 0.2)).astype(np.int32)
    w = rng.random((n, K)).astype(np.float32) * 0.01
    u = rng.random(n).astype(np.float32)
    t1, m1, s1, q1 = sample_fused(jnp.asarray(u), jnp.asarray(d),
                                  jnp.asarray(w), alpha=0.1, tile_t=tile_t,
                                  block_k=block_k, interpret=True)
    t2, m2, s2, q2 = sample_fused(jnp.asarray(u), jnp.asarray(d),
                                  jnp.asarray(w), alpha=0.1, interpret=True)
    np.testing.assert_allclose(m1, m2, rtol=1e-5)
    np.testing.assert_allclose(s1, s2, rtol=1e-4, atol=1e-6)
    assert np.mean(np.asarray(t1) != np.asarray(t2)) < 2e-3


@pytest.mark.parametrize("n,L,K", [(100, 8, 64), (300, 16, 256),
                                   (513, 32, 1000), (64, 4, 33)])
def test_sample_sparse_vs_ref(n, L, K):
    rng = np.random.default_rng(n + L + K)
    idx = np.zeros((n, L), np.int32)
    val = np.zeros((n, L), np.int32)
    for i in range(n):
        nnz = rng.integers(0, L + 1)
        idx[i] = rng.choice(K, L, replace=False)
        val[i, :nnz] = rng.integers(1, 30, nnz)
    packed = pack_pairs(jnp.asarray(idx), jnp.asarray(val))
    W_row = rng.random(K).astype(np.float32) * 0.01
    w_at = jnp.asarray(W_row[idx])
    k1 = jnp.asarray(rng.integers(0, K, n).astype(np.int32))
    a1 = jnp.asarray(rng.random(n).astype(np.float32) * 0.02)
    b1 = jnp.asarray(rng.integers(0, 20, n).astype(np.float32))
    qp = jnp.asarray(rng.random(n).astype(np.float32) * 0.05)
    u = jnp.asarray(rng.random(n).astype(np.float32))
    alpha = 50.0 / K
    tk, nq_k, sp_k = sample_sparse(u, packed, w_at, k1, a1, b1, qp,
                                   alpha=alpha, interpret=True)
    tr_, nq_r, sp_r = ref.sample_sparse_ref(
        u, jnp.asarray(idx), jnp.asarray(val), w_at, k1, a1, b1, qp,
        alpha=alpha)
    np.testing.assert_allclose(sp_k, sp_r, rtol=1e-5, atol=1e-7)
    assert np.array_equal(np.asarray(nq_k), np.asarray(nq_r))
    assert np.mean(np.asarray(tk) != np.asarray(tr_)) < 2e-3


def test_sparse_tail_draw_on_sorted_rows_with_empty_slots():
    """The hybrid state packs D rows sorted with (EMPTY_IDX, 0) padding
    slots. Their S' mass must be the dense one, Σ_{k≠K1} D·Ŵ — an empty
    slot carries none (never 0·NaN) — and every topic a real one."""
    from repro.core.sparse import pack_rows_sorted
    from repro.kernels import ops as kops
    rng = np.random.default_rng(11)
    n, K, L = 300, 64, 24
    d = np.zeros((n, K), np.int32)
    for i in range(n):
        cols = rng.choice(K, rng.integers(1, L // 2), replace=False)
        d[i, cols] = rng.integers(1, 30, cols.size)
    packed, _ = pack_rows_sorted(jnp.asarray(d), L)
    w_rows = rng.random((n, K)).astype(np.float32) * 0.01
    k1 = rng.integers(0, K, n).astype(np.int32)
    b1 = d[np.arange(n), k1].astype(np.float32)
    a1 = w_rows[np.arange(n), k1]
    alpha = 0.1
    w_prime = np.where(np.arange(K) == k1[:, None], 0.0, w_rows)
    qp = alpha * w_prime.sum(axis=1)
    u = jnp.asarray(rng.random(n).astype(np.float32))
    topics, needs_q, _ = kops.sparse_tail_draw(
        u, packed, jnp.asarray(w_rows), jnp.asarray(k1), jnp.asarray(a1),
        jnp.asarray(b1), jnp.asarray(qp, jnp.float32), alpha=alpha,
        interpret=True)
    idx = np.minimum(np.asarray(packed) >> 16 & 0xFFFF, K - 1)
    w_at = jnp.asarray(np.take_along_axis(w_rows, idx, axis=1))
    _, _, s_prime = sample_sparse(
        u, packed, w_at, jnp.asarray(k1), jnp.asarray(a1), jnp.asarray(b1),
        jnp.asarray(qp, jnp.float32), alpha=alpha, interpret=True)
    np.testing.assert_allclose(s_prime, (d * w_prime).sum(axis=1),
                               rtol=1e-5)
    topics = np.asarray(topics)
    assert ((topics >= 0) & (topics < K)).all()
    # Q' is a small share of the mass here; NaN masses send every token there
    assert np.mean(np.asarray(needs_q)) < 0.5


@pytest.mark.parametrize("n,R,K,rpt", [
    (2000, 50, 64, 32),      # narrow rows: pure MXU path
    (5000, 300, 130, 64),    # mixed
    (4096, 1000, 256, 16),   # wide rows: exercises the fallback scatter
    (777, 10, 33, 8),        # unaligned everything
])
def test_histogram_vs_ref(n, R, K, rpt):
    rng = np.random.default_rng(n + R)
    rows = np.sort(rng.integers(0, R, n)).astype(np.int32)
    topics = rng.integers(0, K, n).astype(np.int32)
    w = (rng.random(n) < 0.9).astype(np.int32)
    out = histogram(jnp.asarray(rows), jnp.asarray(topics), jnp.asarray(w),
                    n_rows=R, n_topics=K, tile_t=512, rows_per_tile=rpt,
                    interpret=True)
    want = ref.histogram_ref(jnp.asarray(rows), jnp.asarray(topics),
                             jnp.asarray(w), n_rows=R, n_topics=K)
    assert np.array_equal(np.asarray(out), np.asarray(want))


def test_pallas_update_counts_matches_esca(small_corpus):
    from repro.core import esca, inverted_index
    from repro.kernels import ops as kops
    c = small_corpus
    K = 16
    rng = np.random.default_rng(0)
    topics = jnp.asarray(rng.integers(0, K, c.n_tokens).astype(np.int32))
    mask = jnp.ones(c.n_tokens, jnp.int32)
    wi, di = jnp.asarray(c.word_ids), jnp.asarray(c.doc_ids)
    D0, W0 = esca.update_counts(wi, di, topics, mask, n_docs=c.n_docs,
                                n_words=c.n_words, n_topics=K)
    D1, W1 = kops.update_counts(
        wi, di, topics, mask, jnp.asarray(c.inv_token_idx),
        jnp.asarray(inverted_index.doc_segment_ids(c)),
        n_docs=c.n_docs, n_words=c.n_words, n_topics=K, interpret=True)
    assert np.array_equal(np.asarray(D0), np.asarray(D1))
    assert np.array_equal(np.asarray(W0), np.asarray(W1))


def test_pallas_trainer_e2e(small_corpus):
    """impl=pallas end-to-end: LLPT rises, same direction as the XLA path."""
    from repro.lda.model import LDAConfig
    from repro.lda.trainer import LDATrainer
    cfg = LDAConfig(n_topics=16, tile_size=512, impl="pallas")
    tr = LDATrainer(small_corpus, cfg, _from_engine=True)
    state = tr.init_state()
    llpt0 = tr.evaluate(state)
    for _ in range(8):
        state, stats = tr.step(state)
    llpt1 = tr.evaluate(state)
    assert llpt1 > llpt0 + 0.05 and not np.isnan(llpt1)


def test_sparse_d_sampling_path_matches_reference(small_corpus):
    """ops.sample_tokens_sparse_d (packed-ELL D rows, O(L) per token) draws
    from the same distribution as the dense reference sampler."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.core import esca, three_branch
    from repro.core.sparse import build_sparse_rows
    from repro.kernels import ops as kops
    from repro.lda.model import LDAConfig
    from repro.lda.trainer import LDATrainer

    cfg = LDAConfig(n_topics=16, tile_size=512)
    tr = LDATrainer(small_corpus, cfg, _from_engine=True)
    state = tr.init_state()
    for _ in range(5):
        state, _ = tr.step(state)
    L = int(np.asarray(state.D).astype(bool).sum(1).max())  # max row nnz
    packed_d = build_sparse_rows(state.D, capacity=L)
    W_hat = esca.compute_w_hat(state.W, cfg.beta)
    key = jax.random.PRNGKey(0)
    t_sp, _ = kops.sample_tokens_sparse_d(
        key, tr.word_ids, tr.doc_ids, state.topics, packed_d, state.D,
        W_hat, alpha=cfg.alpha_, interpret=True)
    # same key -> same u; dense exact reference
    u = jax.random.uniform(key, tr.word_ids.shape, dtype=jnp.float32)
    sw = three_branch.word_stats(W_hat, g=2, alpha=cfg.alpha_)
    t_ref, _ = three_branch.exact_three_branch(
        u, tr.word_ids, tr.doc_ids, sw.k[:, 0], state.D, W_hat,
        alpha=cfg.alpha_, tile_size=512)
    # sparse path orders the CDF by ELL slots, dense by topic id — same
    # per-topic mass, different u->topic maps; compare distributions
    h_sp = np.bincount(np.asarray(t_sp), minlength=16) / len(t_sp)
    h_rf = np.bincount(np.asarray(t_ref), minlength=16) / len(t_ref)
    assert 0.5 * np.abs(h_sp - h_rf).sum() < 0.05, (h_sp, h_rf)
    # and the M-branch (skip) decisions agree exactly: same u, same M
    dec = three_branch.skip_phase(u, tr.word_ids, tr.doc_ids, state.D, sw,
                                  g=2, alpha=cfg.alpha_)
    agree = np.asarray(t_sp)[np.asarray(dec.skip)] == \
        np.asarray(dec.k1)[np.asarray(dec.skip)]
    assert agree.all()
