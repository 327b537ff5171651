"""WarpLDA-style Metropolis–Hastings sampling (``sampler="warp"``).

The exact three-branch sampler (core/three_branch.py) pays O(K) or O(L)
per surviving token. WarpLDA (PAPERS.md) replaces the exact draw with a
Metropolis–Hastings chain whose proposals cost O(1) amortized per token:

  * **doc proposal** — q_doc(k) ∝ D[d][k] + α, drawn *positionally*:
    pick a uniformly random token of the same document and reuse its
    (iteration-start) topic, or an α-uniform topic with probability
    Kα/(L_d + Kα). No per-doc table is ever built.
  * **word proposal** — q_word(k) ∝ W̃[v][k], drawn from a Walker alias
    table built over the word's Ŵ row. The table build is O(K) per row
    and amortizes over every draw that touches the row — per *scan* in
    the fused pipeline, per *tile* in the Pallas kernel, where the
    (win_words, K) word-run window already holds the rows resident.

Each token runs ``mh_cycles`` cycles of (doc proposal, word proposal),
i.e. ≥ 2 proposals per token per iteration. Acceptance is classic MH
against the live iteration-start counts: with target

    p(k) ∝ (D[d][k] + α) · Ŵ[v][k]

the doc-proposal ratio collapses to Ŵ[v][t]/Ŵ[v][s] (the (D+α) factors
cancel exactly because the proposal is built from the SAME iteration-
start D snapshot the target uses), and the word-proposal ratio is

    A = [(D[d][t]+α) · Ŵ[v][t] · q̃[v][s]] / [(D[d][s]+α) · Ŵ[v][s] · q̃[v][t]]

where q̃ is the (possibly stale) table distribution. Staleness is
*sound*, not approximate: MH is exact for ANY fixed proposal
distribution, so tables built at scan start stay valid for the whole
scan while the acceptance ratio keeps using them as q̃ (DESIGN.md §12).

Bitwise equality against the exact sampler is the wrong bar for a
different chain; correctness here is pinned by the float64 NumPy
reference (``reference_chain_numpy``) and the stationarity test in
tests/test_warp_sampler.py.
"""

from __future__ import annotations

import functools
from typing import Callable, NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

__all__ = ["AliasTables", "DocIndex", "WarpStats", "build_alias_tables",
           "alias_queues", "run_vose", "build_doc_index", "doc_proposals",
           "word_proposals", "mh_chain", "sample_warp",
           "reference_chain_numpy"]


class AliasTables(NamedTuple):
    """Walker alias tables over each row of a weight matrix.

    ``q`` is the normalized proposal distribution the tables draw from —
    kept because the MH acceptance ratio needs q̃ gathers even after the
    tables go stale (the scan-start snapshot argument above).
    """
    prob: jax.Array    # (R, K) f32 in [0, 1] — keep-slot probability
    alias: jax.Array   # (R, K) int32 — redirect target per slot
    q: jax.Array       # (R, K) f32 — the normalized weights (rows sum to 1)


class DocIndex(NamedTuple):
    """Static doc→token index for the positional doc proposal."""
    start: jax.Array    # (M,) int32 — first slot of each doc in ``perm``
    length: jax.Array   # (M,) int32 — real tokens per doc
    perm: jax.Array     # (n_real,) int32 — token indices sorted by doc


class WarpStats(NamedTuple):
    """Per-iteration MH statistics (NamedTuple: history wants _asdict)."""
    frac_accepted: jax.Array    # tokens that accepted >= 1 proposal
    frac_unchanged: jax.Array   # final topic == iteration-start topic
    n_proposals: jax.Array      # proposals issued per token (2 * mh_cycles)


# ---------------------------------------------------------------------------
# Walker alias tables (vectorized Vose construction)
# ---------------------------------------------------------------------------

def _scatter_prims(R: int, K: int):
    """Row-parallel gather/put on (R, K) arrays via real XLA scatters."""
    rows = jnp.arange(R)

    def gather(arr, idx):
        return arr[rows, idx]

    def put(arr, idx, val, mask):
        # masked-out rows write out of range and are dropped
        safe = jnp.where(mask, idx, K)
        return arr.at[rows, safe].set(val.astype(arr.dtype), mode="drop")

    return gather, put


def _onehot_prims(R: int, K: int):
    """The same gather/put contract with one-hot masks only — no scatter,
    no 1D iota, so the Pallas TPU kernel can run the identical build.
    Values are bit-equal to the scatter primitives: a one-hot masked sum
    adds exact zeros, and a where-write stores the same f32/int32 value.
    Per-row vectors are (R, 1) columns here, the shape Mosaic broadcasts
    across lanes.
    """
    kk = jax.lax.broadcasted_iota(jnp.int32, (R, K), 1)

    def gather(arr, idx):
        sel = kk == idx
        return jnp.sum(jnp.where(sel, arr, jnp.zeros_like(arr)), axis=1,
                       keepdims=True)

    def put(arr, idx, val, mask):
        sel = (kk == idx) & mask
        return jnp.where(sel, val.astype(arr.dtype), arr)

    return gather, put


def alias_queues(scaled: jax.Array):
    """Initial Vose small/large queues for each row of ``scaled`` (= q·K).

    Encoded as fixed (R, K) index arrays plus per-row counts so the build
    loop is a static-shape scan: smalls ascending first (junk after), and
    the large queue likewise. Sort-based, so this runs OUTSIDE the Pallas
    kernel; the kernel receives its window's slice as resident metadata
    (like the tile plan itself) and runs the pairing loop locally.
    """
    R, K = scaled.shape
    is_small = scaled < 1.0
    k_idx = jax.lax.broadcasted_iota(jnp.int32, (R, K), 1)
    pos = k_idx
    n_small = jnp.sum(is_small, axis=1).astype(jnp.int32)
    squeue = jnp.sort(jnp.where(is_small, k_idx, k_idx + K), axis=1)
    squeue = jnp.where(pos < n_small[:, None], squeue, squeue - K)
    lqueue = jnp.sort(jnp.where(is_small, k_idx + K, k_idx), axis=1)
    lqueue = jnp.where(pos < (K - n_small)[:, None], lqueue, lqueue - K)
    return squeue, lqueue, n_small


def run_vose(scaled: jax.Array, squeue: jax.Array, lqueue: jax.Array,
             n_small: jax.Array, *, onehot: bool = False):
    """Vose pairing from precomputed queues → (prob, alias), both (R, K).

    K sequential steps of O(R) row-parallel work (O(R·K) total with the
    scatter primitives). Each step pops one small, fills its slot from
    the head large, and demotes the large to the small queue when its
    residual drops below 1 — the textbook two-queue construction, fully
    deterministic (same weights ⇒ bitwise-identical tables).
    """
    R, K = scaled.shape
    if onehot:
        gather, put = _onehot_prims(R, K)
        if n_small.ndim == 1:           # the kernel passes a column already
            n_small = n_small[:, None]
    else:
        gather, put = _scatter_prims(R, K)
    n_large = (K - n_small).astype(jnp.int32)
    prob0 = jnp.ones((R, K), jnp.float32)
    alias0 = jax.lax.broadcasted_iota(jnp.int32, (R, K), 1)
    zeros = jnp.zeros_like(n_small, jnp.int32)

    def body(_, carry):
        scaled, squeue, s_head, s_tail, l_head, prob, alias = carry
        has = (s_head < s_tail) & (l_head < n_large)
        s = gather(squeue, jnp.clip(s_head, 0, K - 1))
        l = gather(lqueue, jnp.clip(l_head, 0, K - 1))
        sval = gather(scaled, s)
        prob = put(prob, s, sval, has)
        alias = put(alias, s, l, has)
        lval = gather(scaled, l) - (1.0 - sval)
        scaled = put(scaled, l, lval, has)
        demote = has & (lval < 1.0)
        squeue = put(squeue, jnp.clip(s_tail, 0, K - 1), l, demote)
        inc = has.astype(jnp.int32)
        dem = demote.astype(jnp.int32)
        return (scaled, squeue, s_head + inc, s_tail + dem, l_head + dem,
                prob, alias)

    carry = (scaled, squeue, zeros, n_small, zeros, prob0, alias0)
    *_, prob, alias = jax.lax.fori_loop(0, K, body, carry)
    return prob, alias


@jax.jit
def build_alias_tables(weights: jax.Array) -> AliasTables:
    """Alias tables for q(k) ∝ weights[r][k], every row independently.

    Deterministic: the queue order and pairing depend only on the weight
    values, so the same counts always build bitwise-identical tables
    (pinned by the hypothesis property test). Row-independent: building
    a sliced window of rows equals slicing tables built on all rows.
    """
    w = jnp.asarray(weights, jnp.float32)
    K = w.shape[1]
    q = w / jnp.sum(w, axis=1, keepdims=True)
    scaled = q * K
    squeue, lqueue, n_small = alias_queues(scaled)
    prob, alias = run_vose(scaled, squeue, lqueue, n_small)
    return AliasTables(prob=prob, alias=alias, q=q)


# ---------------------------------------------------------------------------
# proposals
# ---------------------------------------------------------------------------

def build_doc_index(doc_ids, mask, n_docs: int) -> DocIndex:
    """Host-side static doc→token index (the corpus layout never moves)."""
    d = np.asarray(doc_ids)
    m = np.asarray(mask).astype(bool)
    real = np.nonzero(m)[0]
    perm = real[np.argsort(d[real], kind="stable")].astype(np.int32)
    length = np.bincount(d[real], minlength=n_docs).astype(np.int32)
    start = np.zeros(n_docs, np.int32)
    np.cumsum(length[:-1], out=start[1:])
    if perm.size == 0:                      # degenerate all-padding corpus
        perm = np.zeros(1, np.int32)
    return DocIndex(start=jnp.asarray(start), length=jnp.asarray(length),
                    perm=jnp.asarray(perm))


def doc_proposals(key, topics, doc_ids, index: DocIndex, *, n_topics: int,
                  alpha: float, n_cycles: int):
    """(n_cycles, n) positional doc proposals — three uniforms per draw.

    P(t = k) = (D̂[d][k] + α) / (L_d + Kα) with D̂ the iteration-start
    counts: the doc term of the MH ratio cancels against the target's.
    """
    n = doc_ids.shape[0]
    u = jax.random.uniform(key, (n_cycles, 3, n), dtype=jnp.float32)
    L = index.length[doc_ids]                                   # (n,)
    pos = index.start[doc_ids][None, :] + jnp.minimum(
        (u[:, 0] * L).astype(jnp.int32), jnp.maximum(L - 1, 0))
    t_pos = topics[index.perm[jnp.clip(pos, 0, index.perm.shape[0] - 1)]]
    p_unif = (n_topics * alpha) / (L.astype(jnp.float32) + n_topics * alpha)
    t_unif = jnp.minimum((u[:, 2] * n_topics).astype(jnp.int32),
                         n_topics - 1)
    return jnp.where((u[:, 1] < p_unif) | (L == 0), t_unif, t_pos)


def word_proposals(key, word_ids, tables: AliasTables, *, n_cycles: int):
    """(n_cycles, n) alias-table word proposals — two uniforms per draw.

    Also returns the raw uniforms so the Pallas path can replay the SAME
    draws against its tile-local tables (bit-equal by row independence).
    """
    n = word_ids.shape[0]
    K = tables.prob.shape[1]
    u = jax.random.uniform(key, (n_cycles, 2, n), dtype=jnp.float32)
    t = alias_draw(u, word_ids, tables.prob, tables.alias, n_topics=K)
    return t, u


def alias_draw(u, word_ids, prob, alias, *, n_topics: int):
    """Draw from per-word alias tables: slot j = ⌊u₀K⌋, keep j if
    u₁ < prob[v][j] else take alias[v][j]. O(1) gathers per draw."""
    j = jnp.minimum((u[:, 0] * n_topics).astype(jnp.int32), n_topics - 1)
    keep = u[:, 1] < prob[word_ids[None, :], j]
    return jnp.where(keep, j, alias[word_ids[None, :], j])


# ---------------------------------------------------------------------------
# the MH accept/reject chain
# ---------------------------------------------------------------------------

def mh_chain(s0, t_doc, t_word, u_acc, *, lookup_d: Callable,
             lookup_w: Callable, lookup_q: Callable, alpha: float,
             return_ratios: bool = False):
    """Run the proposal cycle per token given O(1) lookup closures.

    ``lookup_d(k)`` → D[dᵢ][kᵢ] (f32 counts), ``lookup_w(k)`` → live
    Ŵ[vᵢ][kᵢ], ``lookup_q(k)`` → stale table distribution q̃[vᵢ][kᵢ].
    Acceptance compares u·den < num (no division — the float64 oracle
    replays the identical predicate). Returns (topics, accepted counts)
    and, with ``return_ratios``, the (C, 2, n) acceptance ratios.
    """
    n_cycles = t_doc.shape[0]
    s = s0
    n_acc = jnp.zeros(s0.shape, jnp.int32)
    ratios = []
    for c in range(n_cycles):
        t = t_doc[c]
        num, den = lookup_w(t), lookup_w(s)
        acc = u_acc[c, 0] * den < num
        if return_ratios:
            ratios.append(num / den)
        n_acc += acc
        s = jnp.where(acc, t, s)

        t = t_word[c]
        num = (lookup_d(t) + alpha) * lookup_w(t) * lookup_q(s)
        den = (lookup_d(s) + alpha) * lookup_w(s) * lookup_q(t)
        acc = u_acc[c, 1] * den < num
        if return_ratios:
            ratios.append(num / den)
        n_acc += acc
        s = jnp.where(acc, t, s)
    if return_ratios:
        return s, n_acc, jnp.stack(ratios).reshape(n_cycles, 2, -1)
    return s, n_acc


@functools.partial(jax.jit, static_argnames=("alpha", "n_cycles"))
def sample_warp(key, word_ids, doc_ids, topics, D, W_hat,
                tables: AliasTables, index: DocIndex, *, alpha: float,
                n_cycles: int, mask=None):
    """Full-batch XLA warp sampler (the trainer.step reference path).

    One iteration of the MH chain over every token: proposals, then the
    accept/reject cycle with direct 2D gathers — O(1) work per token, no
    (n, K) row materialization anywhere. Padding tokens (``mask == 0``)
    keep their topic and drop out of the stats — the same treatment the
    fused pipeline's padding skip applies, so the two paths stay
    bit-equal slot for slot.
    """
    kd, kw, ka = jax.random.split(key, 3)
    n = word_ids.shape[0]
    n_topics = W_hat.shape[1]
    t_doc = doc_proposals(kd, topics, doc_ids, index, n_topics=n_topics,
                          alpha=alpha, n_cycles=n_cycles)
    t_word, _ = word_proposals(kw, word_ids, tables, n_cycles=n_cycles)
    u_acc = jax.random.uniform(ka, (n_cycles, 2, n), dtype=jnp.float32)
    s, n_acc = mh_chain(
        topics, t_doc, t_word, u_acc,
        lookup_d=lambda k: D[doc_ids, k].astype(jnp.float32),
        lookup_w=lambda k: W_hat[word_ids, k],
        lookup_q=lambda k: tables.q[word_ids, k],
        alpha=alpha)
    f32 = jnp.float32
    if mask is None:
        m = jnp.ones(n, f32)
    else:
        m = (mask > 0).astype(f32)
        s = jnp.where(mask > 0, s, topics)
        n_acc = jnp.where(mask > 0, n_acc, 0)
    denom = jnp.maximum(jnp.sum(m), 1.0)
    stats = WarpStats(
        frac_accepted=jnp.sum((n_acc > 0).astype(f32) * m) / denom,
        frac_unchanged=jnp.sum((s == topics).astype(f32) * m) / denom,
        n_proposals=jnp.float32(2 * n_cycles))
    return s, stats


# ---------------------------------------------------------------------------
# float64 NumPy oracle (the acceptance-ratio reference)
# ---------------------------------------------------------------------------

def reference_chain_numpy(s0, t_doc, t_word, u_acc, doc_ids, word_ids,
                          D, W_hat, q, alpha: float):
    """The MH chain in float64 NumPy, returning per-proposal ratios.

    Same predicate (u · den < num) as the jax chain; the test compares
    both the f32/f64 acceptance ratios and the final topics away from
    predicate boundaries.
    """
    s = np.asarray(s0, np.int64).copy()
    t_doc = np.asarray(t_doc, np.int64)
    t_word = np.asarray(t_word, np.int64)
    u_acc = np.asarray(u_acc, np.float64)
    d_ids = np.asarray(doc_ids, np.int64)
    w_ids = np.asarray(word_ids, np.int64)
    D = np.asarray(D, np.float64)
    W_hat = np.asarray(W_hat, np.float64)
    q = np.asarray(q, np.float64)
    n_cycles = t_doc.shape[0]
    ratios = np.zeros((n_cycles, 2, s.shape[0]), np.float64)
    for c in range(n_cycles):
        t = t_doc[c]
        num = W_hat[w_ids, t]
        den = W_hat[w_ids, s]
        ratios[c, 0] = num / den
        acc = u_acc[c, 0] * den < num
        s = np.where(acc, t, s)

        t = t_word[c]
        num = (D[d_ids, t] + alpha) * W_hat[w_ids, t] * q[w_ids, s]
        den = (D[d_ids, s] + alpha) * W_hat[w_ids, s] * q[w_ids, t]
        ratios[c, 1] = num / den
        acc = u_acc[c, 1] * den < num
        s = np.where(acc, t, s)
    return s.astype(np.int32), ratios
