"""Public jit'd wrappers around the Pallas kernels.

These are what the trainer / distributed paths call when
``LDAConfig.impl == "pallas"``. On CPU (this container) the kernels run in
interpret mode; on a real TPU backend the same code compiles to Mosaic.

The division of labor (DESIGN.md §2): XLA does the gathers (inverted-index
driven, irregular), Pallas does the O(T·K) / O(T·L) blocked arithmetic.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.core import esca, three_branch
from repro.kernels import histogram as _hist
from repro.kernels import sample_fused as _fused
from repro.kernels import sample_sparse as _sparse
from repro.kernels.runtime import interpret_default

__all__ = ["interpret_default", "sample_tokens", "update_counts",
           "sample_tokens_sparse_d", "sparse_tail_draw",
           "sparse_tail_draw_tiled"]


@functools.partial(jax.jit, static_argnames=("alpha", "tile_size", "interpret"))
def sample_tokens(key, word_ids, doc_ids, old_topics, D, W_hat, *,
                  alpha: float, tile_size: int = 4096,
                  interpret: bool | None = None):
    """Dense-path EZLDA sampling via the fused kernel.

    Gathers (tiled to bound live memory at O(tile·K)), then sample_fused.
    Returns (topics, stats) shaped like three_branch.sample's output.
    """
    if interpret is None:
        interpret = interpret_default()
    n = word_ids.shape[0]
    u = jax.random.uniform(key, (n,), dtype=jnp.float32)
    tile = min(tile_size, n)
    n_pad = (-n) % tile
    u_p = jnp.pad(u, (0, n_pad))
    v_p = jnp.pad(word_ids, (0, n_pad))
    d_p = jnp.pad(doc_ids, (0, n_pad))
    shape = (-1, tile)

    def tile_fn(_, args):
        u_t, v_t, d_t = args
        out = _fused.sample_fused(u_t, D[d_t], W_hat[v_t], alpha=alpha,
                                  interpret=interpret)
        return None, out

    _, (topics, m, s, q) = jax.lax.scan(
        tile_fn, None,
        (u_p.reshape(shape), v_p.reshape(shape), d_p.reshape(shape)))
    topics, m, s, q = (x.reshape(-1)[:n] for x in (topics, m, s, q))
    x = u * (m + s + q)
    in_m = x < m
    in_q = (~in_m) & (x >= m + s)                     # landed past S' segment
    k1 = jnp.argmax(W_hat, axis=-1).astype(jnp.int32)[word_ids]
    stats = three_branch.ThreeBranchStats(
        frac_skipped=jnp.mean(in_m.astype(jnp.float32)),  # kernel = exact path
        frac_m_final=jnp.mean(in_m.astype(jnp.float32)),
        frac_unchanged=jnp.mean((topics == old_topics).astype(jnp.float32)),
        frac_at_max=jnp.mean((topics == k1).astype(jnp.float32)),
        frac_q_branch=jnp.mean(in_q.astype(jnp.float32)),
    )
    return topics, stats


def _q_fallback(u, topics, needs_q, s_prime, w_rows, k1, a1, b1, q_prime,
                alpha):
    """Q'-branch fallback: inverse-CDF over α·Ŵ' for flagged tokens only.

    Uses the kernel's own S' mass, so the fallback target is consistent
    with the needs_q decision (and the O(N·L) host recompute is gone).
    Shared by the plain and tile-scheduled tail draws — same values in ⇒
    same bits out.
    """
    k_total = w_rows.shape[1]
    w_prime = jnp.where(
        jnp.arange(k_total)[None, :] == k1[:, None], 0.0, w_rows)
    m = a1 * (b1 + alpha)
    xq = u * (m + s_prime + q_prime) - m - s_prime
    cq = jnp.cumsum(alpha * w_prime, axis=1)
    topic_q = jnp.minimum(
        jax.vmap(lambda c, x: jnp.searchsorted(c, x, side="right"))(cq, xq),
        k_total - 1).astype(jnp.int32)
    topics = jnp.where(needs_q, topic_q, topics)
    in_m = u * (m + s_prime + q_prime) < m
    return topics, needs_q, in_m


def sparse_tail_draw(u, packed_rows, w_rows, k1, a1, b1, q_prime, *,
                     alpha: float, interpret: bool | None = None):
    """One O(L) three-branch draw per token over packed ELL D rows.

    The building block shared by sample_tokens_sparse_d and the hybrid
    fused pipeline's tail dispatch (train/lda_step.py): the Pallas
    ``sample_sparse`` kernel covers the M and S' branches in O(L) slots,
    then the rare Q' landings finish against α·Ŵ' via one inverse-CDF.
    Args are per-token gathers: packed_rows (C, L); w_rows = Ŵ[word] (C, K);
    k1/a1/b1/q_prime per-token word/doc stats. Returns (topics, needs_q,
    in_m).
    """
    idx = (packed_rows.view(jnp.uint32) >> 16).astype(jnp.int32)
    # an empty slot (EMPTY_IDX, 0) reads a clipped column: its mass is 0
    # either way, where the default fill would make it 0 * NaN
    w_at = jnp.take_along_axis(w_rows, idx, axis=1, mode="clip")
    topics, needs_q, s_prime = _sparse.sample_sparse(
        u, packed_rows, w_at, k1, a1, b1, q_prime, alpha=alpha,
        interpret=interpret)
    return _q_fallback(u, topics, needs_q, s_prime, w_rows, k1, a1, b1,
                       q_prime, alpha)


def sparse_tail_draw_tiled(u, packed_rows, w_hat, word_ids, first_word,
                           k1_w, a1_w, q_prime_w, b1, *, alpha: float,
                           win_words: int, interpret: bool | None = None):
    """Tile-scheduled sparse tail draw (paper SSV-A made live, DESIGN SS9).

    Instead of per-token gathered Ŵ rows and word stats, the tile's
    word-run metadata (``first_word``, static ``win_words`` window bound)
    selects ONE window of Ŵ / K1 / a1 / Q' shared by the whole chunk; the
    ``sample_sparse_tiled`` kernel resolves per-token values by local word
    offset. The Q' fallback reads the same windows, so the result is
    bit-equal to ``sparse_tail_draw`` on the per-token gathers. Callers
    guarantee the chunk's word span fits the window (cond-guarded in
    train/lda_step.py).
    """
    v_total, k_total = w_hat.shape
    win = int(min(win_words, v_total))
    first = jnp.clip(jnp.asarray(first_word, jnp.int32), 0, v_total - win)
    local = jnp.clip(word_ids.astype(jnp.int32) - first, 0, win - 1)
    w_win = jax.lax.dynamic_slice(w_hat, (first, 0), (win, k_total))
    rows = w_win[local]        # ONE (C, K) materialization from the window
    topics, needs_q, s_prime = _sparse.sample_sparse_tiled(
        u, packed_rows, jnp.take_along_axis(
            rows,
            (packed_rows.view(jnp.uint32) >> 16).astype(jnp.int32), axis=1,
            mode="clip"),
        word_ids, first, k1_w, a1_w, q_prime_w, b1, alpha=alpha,
        win_words=win_words, interpret=interpret)
    k1_win = jax.lax.dynamic_slice(k1_w, (first,), (win,))
    a1_win = jax.lax.dynamic_slice(a1_w, (first,), (win,))
    qp_win = jax.lax.dynamic_slice(q_prime_w, (first,), (win,))
    return _q_fallback(u, topics, needs_q, s_prime, rows,
                       k1_win[local], a1_win[local], b1, qp_win[local],
                       alpha)


@functools.partial(jax.jit, static_argnames=(
    "alpha", "g", "interpret"))
def sample_tokens_sparse_d(key, word_ids, doc_ids, old_topics,
                           packed_d_rows, D, W_hat, *, alpha: float,
                           g: int = 2, interpret: bool | None = None):
    """Sparse-D path: O(L) S' kernel + per-word Q' fallback (§IV-C).

    ``packed_d_rows``: (M, L) int32 ELL rows of D (16/16 packed). The Q'
    branch (rare) falls back to the dense CDF on just those tokens — here via
    the exact reference; a converged corpus sends <1% of tokens there.
    """
    if interpret is None:
        interpret = interpret_default()
    n = word_ids.shape[0]
    u = jax.random.uniform(key, (n,), dtype=jnp.float32)
    stats_w = three_branch.word_stats(W_hat, g=g, alpha=alpha)
    k1 = stats_w.k[:, 0][word_ids]
    a1 = stats_w.a[:, 0][word_ids]
    b1 = D[doc_ids, k1].astype(jnp.float32)
    q_prime = stats_w.q_prime[word_ids]
    rows = packed_d_rows[doc_ids]                          # (N, L)
    # Real per-branch fractions from the kernel outputs: the M branch is
    # x < M (exact masses, no estimate phase in this path), the Q' branch is
    # the kernel's needs_q flag, and frac_at_max comes from the final topics.
    topics, needs_q, in_m = sparse_tail_draw(
        u, rows, W_hat[word_ids], k1, a1, b1, q_prime, alpha=alpha,
        interpret=interpret)
    stats = three_branch.ThreeBranchStats(
        frac_skipped=jnp.mean(in_m.astype(jnp.float32)),  # kernel = exact path
        frac_m_final=jnp.mean(in_m.astype(jnp.float32)),
        frac_unchanged=jnp.mean((topics == old_topics).astype(jnp.float32)),
        frac_at_max=jnp.mean((topics == k1).astype(jnp.float32)),
        frac_q_branch=jnp.mean(needs_q.astype(jnp.float32)),
    )
    return topics, stats


@functools.partial(jax.jit, static_argnames=(
    "n_docs", "n_words", "n_topics", "interpret"))
def update_counts(word_ids, doc_ids, topics, mask, inv_token_idx,
                  doc_segment_ids, *, n_docs: int, n_words: int,
                  n_topics: int, interpret: bool | None = None):
    """Count rebuild via the MXU histogram kernel (W word-sorted, D doc-major).

    Drop-in for esca.update_counts (the oracle); the doc-major reorder is the
    inverted-index scan of §IV-C.
    """
    if interpret is None:
        interpret = interpret_default()
    w = jnp.where(mask > 0, 1, 0).astype(jnp.int32)
    W = _hist.histogram(word_ids, topics, w, n_rows=n_words,
                        n_topics=n_topics, interpret=interpret)
    topics_dm = topics[inv_token_idx]
    w_dm = w[inv_token_idx]
    D = _hist.histogram(doc_segment_ids, topics_dm, w_dm, n_rows=n_docs,
                        n_topics=n_topics, interpret=interpret)
    return D, W
