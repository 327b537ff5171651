"""Multi-device EZLDA (paper §V-B) + beyond-paper topic-axis model parallelism.

Paper-faithful mapping (DESIGN.md §6):
  * documents → chunks (greedy token-balanced; §V-B observes ≤5% imbalance);
    each (pod, data) shard owns one chunk: its T slice and its D rows.
  * W is replicated over (pod, data) — each shard keeps a canonical copy —
    and rebuilt each iteration by **summing the per-shard histograms and
    broadcasting** the result (= one ``psum``), exactly the paper's multi-GPU
    update.

Device-level workload balancing (``config.balance == "tiles"``, paper §V-A
applied at shard granularity, DESIGN.md SS9): greedy *document* chunking
cannot split a document, so one giant document — or a power-law head word
riding inside most documents — can still serialize a shard. With tiles on,
``core/balance.assign_token_shards`` assigns TOKENS to shards through word
runs of the word-sorted list, dissecting any >threshold word across shards
(the paper's huge-word dissection, at the device level). Documents whose
tokens land on several shards get their D row REPLICATED on each of them:
every replica holds the full global row (sampling semantics unchanged),
and each iteration the shared rows' ±1 deltas are summed over the data
axes by one extra psum — the same sum+broadcast discipline W already uses,
restricted to the dissection boundary set. Dense format only (packed
per-shard D rows cannot absorb remote dense deltas scatter-free).

Beyond-paper (what the paper says GPU LDA could not do — §I-A: LightLDA-style
model parallelism needs hash tables): shard the **topic axis** of W/Ŵ/D over
the ``model`` mesh axis and sample with a *two-level inverse-CDF*:

  1. every model shard computes its local mass over its topic block
     (K1 excluded): ``L_s = Σ_{k∈block, k≠K1} (D[d][k]+α)·Ŵ[v][k]``;
  2. shard masses are all-gathered (one f32 per token per shard);
  3. the winning shard = inverse-CDF over shard masses; within it the local
     CDF picks the topic; a one-hot psum publishes the winner.

The three-branch skip distributes too: per-word tops are local-top-(g+1)
→ all_gather → global re-top; b_i = psum of a masked local D lookup. The ΔW
all-reduce then moves K/P_model columns per shard — collective bytes drop by
the model-parallel degree versus the paper's full-W sum+broadcast (measured
in EXPERIMENTS.md §Perf).

All collectives are jax.lax primitives inside one shard_map, so the multi-pod
dry-run lowers this exact code path.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map as _shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core import balance as balance_mod
from repro.core import esca, sparse, three_branch
from repro.core import llpt as llpt_mod
from repro.lda import invariants
from repro.lda.corpus import Corpus, chunk_documents
from repro.lda.model import HybridLayout, LDAConfig
from repro.runtime import chaos
from repro.runtime.sharding import batch_axes

__all__ = ["ShardedCorpus", "shard_corpus", "DistLDAState",
           "DistHybridState", "DistStreamState", "DistLDATrainer",
           "PSStreamState", "PSDistTrainer"]


# ---------------------------------------------------------------------------
# host-side partitioning (the paper's chunking, §IV-A/§V-B)
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class ShardedCorpus:
    """Chunked corpus, padded to uniform per-shard length.

    Arrays carry a leading shard axis S = n_data_shards; doc ids are LOCAL
    row indices into the shard's D block (plus a global doc map for eval).
    """
    word_ids: np.ndarray      # (S, N_loc) int32 — word-sorted within shard
    doc_ids: np.ndarray       # (S, N_loc) int32 — local doc rows
    mask: np.ndarray          # (S, N_loc) int32
    doc_map: np.ndarray       # (S, M_loc) int64 — local row → global doc id
    docs_per_shard: np.ndarray  # (S,) int64
    global_pos: np.ndarray    # (S, N_loc) int64 — slot → global token index
                              # (pads point at token 0 with mask 0); makes
                              # checkpoints shard-layout independent (elastic)
    n_words: int
    m_local: int              # D rows per shard (padded)
    n_shards: int
    # balance="tiles" extras (None under document chunking): docs split
    # across shards by token-level assignment get REPLICATED D rows, glued
    # by a per-iteration delta psum over a global shared-doc slot list.
    owns: np.ndarray | None = None         # (S, M_loc) int32 — 1 iff this
                                           # shard is the doc's gather owner
    shared_slot: np.ndarray | None = None  # (S, N_loc) int32 — token's slot
                                           # in the shared-doc list, or
                                           # n_shared (sentinel)
    shared_rows: np.ndarray | None = None  # (S, n_shared) int32 — shared doc
                                           # j's local row, or M_loc sentinel

    @property
    def tokens_per_shard(self) -> np.ndarray:
        return self.mask.sum(axis=1)


def shard_corpus(corpus: Corpus, n_shards: int,
                 pad_multiple: int = 1024, balance: str = "none",
                 dissect_threshold: int | None = None) -> ShardedCorpus:
    if balance == "tiles":
        tok_chunk, _loads = balance_mod.assign_token_shards(
            corpus, n_shards, dissect_threshold)
    else:
        assign = chunk_documents(corpus, n_shards)        # (M,) chunk per doc
        tok_chunk = assign[corpus.doc_ids]                # (N,)
    n_loc, m_loc = 1, 1
    per_shard: list[tuple[np.ndarray, np.ndarray, np.ndarray]] = []
    doc_maps = []
    for s in range(n_shards):
        sel = np.nonzero(tok_chunk == s)[0]
        w = corpus.word_ids[sel]
        d = corpus.doc_ids[sel]
        docs = np.unique(d)
        local = np.searchsorted(docs, d)
        order = np.argsort(w, kind="stable")              # keep word-sorted T
        per_shard.append((w[order], local[order].astype(np.int32),
                          sel[order]))
        doc_maps.append(docs)
        n_loc = max(n_loc, len(w))
        m_loc = max(m_loc, len(docs))
    n_loc = -(-n_loc // pad_multiple) * pad_multiple
    W = np.zeros((n_shards, n_loc), np.int32)
    Dv = np.zeros((n_shards, n_loc), np.int32)
    Mk = np.zeros((n_shards, n_loc), np.int32)
    DM = np.zeros((n_shards, m_loc), np.int64)
    GP = np.zeros((n_shards, n_loc), np.int64)
    nd = np.zeros(n_shards, np.int64)
    for s, (w, d, gp) in enumerate(per_shard):
        W[s, :len(w)] = w
        W[s, len(w):] = corpus.n_words - 1                # keep sorted
        Dv[s, :len(d)] = d
        Mk[s, :len(w)] = 1
        DM[s, :len(doc_maps[s])] = doc_maps[s]
        GP[s, :len(gp)] = gp
        nd[s] = len(doc_maps[s])
    sc = ShardedCorpus(word_ids=W, doc_ids=Dv, mask=Mk, doc_map=DM,
                       docs_per_shard=nd, global_pos=GP,
                       n_words=corpus.n_words,
                       m_local=m_loc, n_shards=n_shards)
    if balance != "tiles":
        return sc

    # -- shared-doc bookkeeping (dissected documents) ----------------------
    # owner = lowest shard holding the doc: gathers count each row once
    owner = np.full(corpus.n_docs, -1, np.int64)
    for s in range(n_shards):
        fresh = doc_maps[s][owner[doc_maps[s]] < 0]
        owner[fresh] = s
    occ = np.bincount(np.concatenate(doc_maps) if doc_maps else
                      np.zeros(0, np.int64), minlength=corpus.n_docs)
    shared_global = np.nonzero(occ > 1)[0]                # global doc ids
    n_shared = max(len(shared_global), 1)                 # keep shapes >0
    slot_of_doc = np.full(corpus.n_docs, n_shared, np.int64)
    slot_of_doc[shared_global] = np.arange(len(shared_global))
    owns = np.zeros((n_shards, m_loc), np.int32)
    SS = np.full((n_shards, n_loc), n_shared, np.int32)
    SR = np.full((n_shards, n_shared), m_loc, np.int32)
    for s in range(n_shards):
        docs = doc_maps[s]
        owns[s, :len(docs)] = (owner[docs] == s)
        # token → shared slot, through the SAME global-position ordering
        # the token arrays above were built from
        gp = per_shard[s][2]
        SS[s, :len(gp)] = slot_of_doc[corpus.doc_ids[gp]]
        # shared doc j → local row on this shard (or the M_loc sentinel)
        if len(shared_global) and len(docs):
            pos = np.searchsorted(docs, shared_global)
            here = (pos < len(docs)) & (docs[np.minimum(pos, len(docs) - 1)]
                                        == shared_global)
            SR[s, :len(shared_global)] = np.where(here, pos, m_loc)
    return dataclasses.replace(sc, owns=owns, shared_slot=SS,
                               shared_rows=SR)


# ---------------------------------------------------------------------------
# state
# ---------------------------------------------------------------------------

@functools.partial(jax.tree_util.register_dataclass,
                   data_fields=["topics", "D", "W", "key", "iteration"],
                   meta_fields=[])
@dataclasses.dataclass(frozen=True)
class DistLDAState:
    topics: jax.Array     # (S, N_loc) int32, sharded over data axes
    D: jax.Array          # (S, M_loc, K) int32, sharded (data, ·, model)
    W: jax.Array          # (V, K) int32, replicated over data, model-sharded
    key: jax.Array
    iteration: jax.Array


@dataclasses.dataclass
class _DistEpochCarry:
    """Open-epoch device state of the streamed distributed trainer:
    the epoch's per-word/word-stat arrays (fixed during the epoch) and
    the accumulated per-device count deltas."""
    derived: tuple                 # (W_hat, g_vals, g_idx, q_prime, len_tot)
    deltas: tuple                  # (dD, dW[, d_shared]) — per-device
    u_host: np.ndarray | None = None  # epoch uniforms, host-staged (S, R·L)
    stats_parts: list = dataclasses.field(default_factory=list)
    n_surv: float = 0.0
    stat_sums: np.ndarray = dataclasses.field(
        default_factory=lambda: np.zeros(5, np.float64))


@dataclasses.dataclass
class DistStreamState:
    """Streamed multi-device training state (corpus_residency="streamed").

    The token-side state lives HOST-side — ``host_topics`` is
    (S, R·L) with each device's token slice split into R equal
    sub-shards — and streams through the devices one sub-shard column
    block at a time; only the count state stays device-resident:
    ``counts`` is ``(D, W)`` for the dense format or
    ``(D_packed, W_head, W_tail, overflow)`` for the hybrid one.
    """
    host_topics: np.ndarray
    counts: tuple
    key: jax.Array
    iteration: int
    cursor: int = 0
    epoch: _DistEpochCarry | None = None

    @property
    def topics(self) -> np.ndarray:
        """Host-side topics view (duck-types the resident states for
        consumers that only read/block on .topics)."""
        return self.host_topics


@functools.partial(jax.tree_util.register_dataclass,
                   data_fields=["topics", "D", "W_head", "W_tail",
                                "overflow", "key", "iteration"],
                   meta_fields=[])
@dataclasses.dataclass(frozen=True)
class DistHybridState:
    """Hybrid-format multi-device state (config.format == "hybrid").

    The per-shard D chunk is packed ELL (the shard owns its documents, so
    its rows pack independently); HybridW is REPLICATED over the data axes
    and maintained by the paper's §V-B sum+broadcast, carried as a delta
    psum that lands back in the packed layout each iteration. Topic-axis
    model parallelism is dense-format-only (packed slots hold global topic
    ids, which do not block-partition), so the model mesh axis must be 1.
    ``overflow`` is the global (psum'd) count of packed updates any shard
    could not place — the same corruption tripwire as
    SparseLDAState.overflow, 0 by the capacity-bound construction.
    """
    topics: jax.Array               # (S, N_loc) int32, data-sharded
    D: jax.Array                    # (S, M_loc, L) int32 packed ELL
    W_head: jax.Array               # (V_dense, K) int32, replicated
    W_tail: tuple[jax.Array, ...]   # packed tail buckets, replicated
    overflow: jax.Array             # () int32, replicated tripwire
    key: jax.Array
    iteration: jax.Array


# ---------------------------------------------------------------------------
# the per-shard step (runs inside shard_map)
# ---------------------------------------------------------------------------

def _word_phase(W, *, cfg: LDAConfig, model_axis: str, n_words: int,
                g: int, kb0, k_local: int, colsum=None):
    """Per-word epoch quantities: Ŵ + distributed top-(g+1) + Q'.

    Extracted from the iteration step so the streamed path can compute
    them ONCE per epoch (they depend only on W, fixed within an epoch)
    while the resident path keeps calling it per iteration — same ops,
    same collectives, bit-identical results either way.

    ``colsum`` overrides the internally-computed per-topic column sum
    for callers whose ``W`` is only a row *window* of the global matrix
    (the parameter-server paged path): the global sum is pulled from the
    server as exact int32 and converted to f32 — identical bits to the
    f32-accumulated sum over full W while the total token count stays
    below 2**24, since every partial sum is an exactly-representable
    integer (DESIGN.md §15).
    """
    if colsum is None:
        colsum = jnp.sum(W, axis=0, dtype=jnp.float32)
    W_hat = (W.astype(jnp.float32) + cfg.beta) / (colsum + n_words * cfg.beta)

    # --- per-word tops: local top-(g+1) → all_gather over model → re-top
    loc_vals, loc_idx = jax.lax.top_k(W_hat, min(g + 1, k_local))
    loc_idx = loc_idx + kb0
    all_vals = jax.lax.all_gather(loc_vals, model_axis)   # (Pm, V, g+1)
    all_idx = jax.lax.all_gather(loc_idx, model_axis)
    cat_vals = jnp.moveaxis(all_vals, 0, 1).reshape(W.shape[0], -1)
    cat_idx = jnp.moveaxis(all_idx, 0, 1).reshape(W.shape[0], -1)
    g_vals, g_pos = jax.lax.top_k(cat_vals, g + 1)        # (V, g+1) global
    g_idx = jnp.take_along_axis(cat_idx, g_pos, axis=1).astype(jnp.int32)
    wsum = jax.lax.psum(jnp.sum(W_hat, axis=-1), model_axis)
    q_prime_w = cfg.alpha_ * (wsum - g_vals[:, 0])        # (V,)
    return W_hat, g_vals, g_idx, q_prime_w


def _token_sweep(u, word_ids, doc_ids, d_rows, len_tot, W_hat, g_vals,
                 g_idx, q_prime_w, *, alpha: float, g: int, kb0,
                 k_local: int, my, model_axis: str, tile: int):
    """Skip phase + combined-sweep phase 2 for one batch of tokens.

    Per-token work only (gathers against the epoch/iteration-start
    counts and word stats), so the streamed path can run it per token
    sub-shard and the resident path over the whole slice — identical
    per-token results. ``d_rows(doc_ids)`` gives the tokens' (·, K_loc)
    D rows. The sweep runs one ``tile`` of tokens at a time: it holds
    (tile, K_loc) rows, and a whole shard's would not fit a chip.
    Returns (new_topics, skip, in_m, k1).
    """
    def sweep(idx):
        d_t = doc_ids[idx]
        return _tile_sweep(u[idx], word_ids[idx], d_t, d_rows(d_t), len_tot,
                           W_hat, g_vals, g_idx, q_prime_w, alpha=alpha,
                           g=g, kb0=kb0, k_local=k_local, my=my,
                           model_axis=model_axis)

    return three_branch.map_token_tiles(
        sweep, jnp.arange(u.shape[0], dtype=jnp.int32), tile)


def _tile_sweep(u, word_ids, doc_ids, d_tok, len_tot, W_hat, g_vals, g_idx,
                q_prime_w, *, alpha: float, g: int, kb0, k_local: int, my,
                model_axis: str):
    """``_token_sweep``'s body for one tile, with its D rows ``d_tok``."""
    # --- per-token skip phase (Eq 8-10); b_i via masked-lookup psum
    a = g_vals[word_ids]                                  # (N, g+1)
    ktop = g_idx[word_ids][:, :g]                         # (N, g)
    rel = ktop - kb0
    in_blk = (rel >= 0) & (rel < k_local)
    b_loc = jnp.where(
        in_blk,
        jnp.take_along_axis(d_tok, jnp.clip(rel, 0, k_local - 1),
                            axis=1), 0).astype(jnp.float32)
    b = jax.lax.psum(b_loc, model_axis)                   # (N, g)
    len_d = len_tot[doc_ids]
    m_mass = a[:, 0] * (b[:, 0] + alpha)                  # Eq 8
    head = jnp.sum(a[:, 1:g] * b[:, 1:g], axis=-1)
    s_est = head + a[:, g] * (len_d - jnp.sum(b, axis=-1))
    q_tok = q_prime_w[word_ids]
    skip = u * (m_mass + s_est + q_tok) < m_mass
    k1 = g_idx[word_ids][:, 0]

    # --- phase 2: two-level inverse-CDF over model shards (combined sweep)
    d_rows = d_tok.astype(jnp.float32)                    # (N, K_loc)
    w_rows = W_hat[word_ids]                              # (N, K_loc)
    k_global = kb0 + jnp.arange(k_local)[None, :]
    mass = jnp.where(k_global == k1[:, None], 0.0,
                     (d_rows + alpha) * w_rows)           # k ≠ K1
    l_mine = jnp.sum(mass, axis=1)                        # (N,) local mass
    l_all = jax.lax.all_gather(l_mine, model_axis)        # (Pm, N)
    pm = l_all.shape[0]        # static axis size (jax.lax.axis_size compat)
    cum_before = jnp.sum(
        jnp.where(jnp.arange(pm)[:, None] < my, l_all, 0.0), axis=0)
    total = m_mass + jnp.sum(l_all, axis=0)
    x = u * total
    tgt = x - m_mass - cum_before                         # local CDF target
    cdf = jnp.cumsum(mass, axis=1)
    hit = cdf > tgt[:, None]
    found = jnp.any(hit, axis=1) & (tgt >= 0) & (x >= m_mass) \
        & (tgt < l_mine)
    pick = kb0 + jnp.argmax(hit, axis=1).astype(jnp.int32)
    claimed = jax.lax.psum(found.astype(jnp.int32), model_axis)
    topic_win = jax.lax.psum(jnp.where(found, pick, 0), model_axis)
    # fp-edge: zero or multiple claims → fall back to K1 (measure-zero)
    topic_exact = jnp.where(claimed == 1, topic_win, k1)
    in_m = x < m_mass
    new_topics = jnp.where(skip | in_m, k1, topic_exact).astype(jnp.int32)
    return new_topics, skip, in_m, k1


def _dist_step(word_ids, doc_ids, mask, state, *,
               cfg: LDAConfig, data_axes: tuple[str, ...], model_axis: str,
               n_words: int, m_local: int, g: int,
               layout: HybridLayout | None = None, shared=None):
    """One EZLDA iteration for one (data, model) shard.

    Inputs arrive with the shard axes stripped: word_ids (1, N_loc),
    D (1, M_loc, K_loc), W (V, K_loc) where K_loc = K / P_model. With
    ``layout`` set (hybrid format, model axis = 1) the state carries packed
    D rows and HybridW; the sampling sweep densifies the gathered per-token
    rows (exact integers, so the trajectory is bit-equal to the dense
    format) and the update lands back in the packed layout.

    ``shared`` (balance="tiles" only) is ``(shared_slot (1, N_loc),
    shared_rows (1, n_shared))``: docs dissected across data shards keep a
    full replica of their D row on every holder, and the replicas are kept
    identical by one psum of the shared rows' ±1 deltas per iteration
    (module docstring, DESIGN.md SS9).
    """
    word_ids, doc_ids, mask = word_ids[0], doc_ids[0], mask[0]
    topics = state.topics[0]
    if layout is None:
        D = state.D[0]
        W = state.W
        d_rows = lambda d: D[d]                           # noqa: E731
        len_rows = jnp.sum(D, axis=-1, dtype=jnp.float32)   # (M_loc,)
    else:
        d_packed = state.D[0]                             # (M_loc, L)
        W = layout.densify_w(state.W_head, state.W_tail)  # (V, K) exact
        d_rows = lambda d: sparse.densify_rows(           # noqa: E731
            d_packed[d], layout.n_topics)
        # per-doc length from the packed val fields: O(M_loc·L), exact ints
        len_rows = jnp.sum(sparse.unpack_pairs(d_packed)[1],
                           axis=-1).astype(jnp.float32)
    k_local = W.shape[1]
    my = jax.lax.axis_index(model_axis)
    kb0 = my * k_local
    alpha = cfg.alpha_
    n = word_ids.shape[0]

    key = jax.random.fold_in(state.key, state.iteration)
    # identical u across the model axis of one data shard; distinct per data
    for ax in data_axes:
        key = jax.random.fold_in(key, jax.lax.axis_index(ax))
    u = jax.random.uniform(key, (n,), dtype=jnp.float32)

    # --- Ŵ + per-word tops + Q' (colsum is per-topic → no comm for Ŵ)
    W_hat, g_vals, g_idx, q_prime_w = _word_phase(
        W, cfg=cfg, model_axis=model_axis, n_words=n_words, g=g,
        kb0=kb0, k_local=k_local)

    # --- per-token skip phase + combined-sweep phase 2
    len_tot = jax.lax.psum(len_rows, model_axis)
    new_topics, skip, in_m, k1 = _token_sweep(
        u, word_ids, doc_ids, d_rows, len_tot, W_hat, g_vals, g_idx,
        q_prime_w, alpha=alpha, g=g, kb0=kb0, k_local=k_local, my=my,
        model_axis=model_axis, tile=cfg.tile_size)

    # --- update: incremental ±1 deltas at changed tokens only (the fused
    # step's delta update, per shard). Each token subtracts its old topic and
    # adds its new one within this shard's column block; D updates in place
    # (donation-friendly) and the W all-reduce carries a delta histogram —
    # identical to the §V-B sum+broadcast because every data shard holds the
    # same replica of W. Both matrices stay exactly equal to a full rebuild.
    wgt = mask.astype(jnp.int32)

    def _blk(t):
        rel = t - kb0
        in_blk = (rel >= 0) & (rel < k_local)
        return jnp.clip(rel, 0, k_local - 1), jnp.where(in_blk, wgt, 0)

    old_rel, w_old = _blk(topics)
    t_rel, w_new = _blk(new_topics)
    with jax.named_scope("lda.count_update"):
        dW_local = jnp.zeros((n_words, k_local), jnp.int32
                             ).at[word_ids, old_rel].add(-w_old
                             ).at[word_ids, t_rel].add(w_new)
    with jax.named_scope("lda.w_psum"):
        dW = jax.lax.psum(dW_local, data_axes)            # delta all-reduce
    if layout is None:
        with jax.named_scope("lda.count_update"):
            D_new = D.at[doc_ids, old_rel].add(-w_old) \
                     .at[doc_ids, t_rel].add(w_new)
        if shared is not None:
            # Dissected docs (balance="tiles"): every holder applied its
            # LOCAL deltas above; add the other shards' deltas so each
            # replica stays the full global row. One psum over the shared
            # slot list — the D analogue of W's §V-B sum+broadcast.
            ss, srows = shared[0][0], shared[1][0]         # (N,), (n_sh,)
            n_sh = srows.shape[0]
            dsh = jnp.zeros((n_sh + 1, k_local), jnp.int32) \
                .at[ss, old_rel].add(-w_old) \
                .at[ss, t_rel].add(w_new)[:n_sh]           # sentinel row off
            remote = jax.lax.psum(dsh, data_axes) - dsh
            D_new = D_new.at[srows].add(remote, mode="drop")
        W_new = W + dW
    else:
        # Packed per-shard D: topic moves land as ±1 slot updates (changed
        # tokens only — unchanged tokens are a no-op in both layouts). The
        # drop count psums into the replicated overflow tripwire.
        chg = wgt * (topics != new_topics).astype(jnp.int32)
        with jax.named_scope("lda.count_update"):
            D_new, drop = sparse.ell_apply_deltas(
                d_packed, doc_ids, topics, new_topics, chg)
        overflow = state.overflow + jax.lax.psum(drop, data_axes)
        # Replicated HybridW: the identical psum'd delta lands on every
        # data shard; the tail repacks from the updated dense rows (exact —
        # bucket capacities are nnz upper bounds, so top_k loses nothing).
        w_full = W + dW
        w_head_new, w_tail_new = layout.split_w(w_full)

    fmask = mask.astype(jnp.float32)
    denom = jax.lax.psum(jnp.sum(fmask), data_axes)
    def _avg(v):
        return jax.lax.psum(jnp.sum(v * fmask), data_axes) / denom
    stats = three_branch.ThreeBranchStats(
        frac_skipped=_avg(skip.astype(jnp.float32)),
        frac_m_final=_avg((skip | in_m).astype(jnp.float32)),
        frac_unchanged=_avg((new_topics == topics).astype(jnp.float32)),
        frac_at_max=_avg((new_topics == k1).astype(jnp.float32)),
        frac_q_branch=jnp.float32(0.0),   # combined sweep: not attributed
        # the combined sweep draws every token: no survivor compaction
        frac_phase2_slots=_avg(jnp.ones_like(fmask)),
    )
    if layout is None:
        new_state = DistLDAState(
            topics=new_topics[None], D=D_new[None], W=W_new,
            key=state.key, iteration=state.iteration + 1)
    else:
        new_state = DistHybridState(
            topics=new_topics[None], D=D_new[None], W_head=w_head_new,
            W_tail=w_tail_new, overflow=overflow, key=state.key,
            iteration=state.iteration + 1)
    return new_state, stats


def _dp_sample(word_ids, doc_ids, mask, gpos, topics, D, W_hat, key, *,
               plan: three_branch.Plan, alpha: float):
    """The draw of a data shard of the resident dense data-parallel path
    (model axis 1, document chunking): the single backend's draw, and no
    collective.

    The key advances as ``LDATrainer.step``'s does (``key, sub =
    split(key)``), and the shard's tokens take their values of the one
    ``uniform(sub, (N,))`` from their global positions ``gpos`` (scope
    ``lda.uniforms``). The single backend's sampler then draws them
    (``three_branch.sample_from_uniforms``, survivor compaction
    included) against ``W_hat``, made outside this program from the
    replicated W as on one chip: each document is whole on one shard, so
    its D row is the global row, and W is the global W, so every token
    draws the topic the single backend draws. Pad slots draw too and
    count nowhere. Returns the new topics, the shard's stats (per-token
    stats summed over its real tokens) and the next key.
    """
    gpos = gpos[0]
    fmask = mask[0].astype(jnp.float32)
    key, sub = jax.random.split(key)
    with jax.named_scope("lda.uniforms"):
        u = three_branch.uniforms_at(sub, gpos)
    new_topics, st = three_branch.sample_from_uniforms(
        u, plan, word_ids[0], doc_ids[0], topics[0], D[0], W_hat,
        alpha=alpha, token_mean=lambda v: jnp.sum(v * fmask))
    return (new_topics[None],
            jax.tree.map(lambda v: jnp.asarray(v, jnp.float32)[None], st),
            key)


def _dp_update(word_ids, doc_ids, mask, topics, new_topics, D, W, st, key,
               iteration, *, data_axes: tuple[str, ...]):
    """The count update of a data shard after ``_dp_sample``: ±1 deltas
    (scope ``lda.count_update``), W's through one all-reduce
    (``lda.w_psum``). The stats become means over the real tokens of
    all shards; the phase-2 slot share and the branch taken, means over
    the shards. Returns the next state and the stats."""
    word_ids, doc_ids, mask = word_ids[0], doc_ids[0], mask[0]
    topics, new_topics, D = topics[0], new_topics[0], D[0]
    wgt = mask.astype(jnp.int32)
    with jax.named_scope("lda.count_update"):
        dW_local = jnp.zeros_like(W).at[word_ids, topics].add(-wgt) \
                                    .at[word_ids, new_topics].add(wgt)
        D_new = D.at[doc_ids, topics].add(-wgt).at[doc_ids, new_topics] \
                 .add(wgt)
    with jax.named_scope("lda.w_psum"):
        dW = jax.lax.psum(dW_local, data_axes)            # delta all-reduce
    st = jax.tree.map(lambda v: v[0], st)
    denom = jax.lax.psum(jnp.sum(mask.astype(jnp.float32)), data_axes)

    def tokens(v):
        return jax.lax.psum(v, data_axes) / denom

    stats = three_branch.ThreeBranchStats(
        frac_skipped=tokens(st.frac_skipped),
        frac_m_final=tokens(st.frac_m_final),
        frac_unchanged=tokens(st.frac_unchanged),
        frac_at_max=tokens(st.frac_at_max),
        frac_q_branch=jnp.float32(0.0),   # not attributed, as on one chip
        frac_phase2_slots=jax.lax.pmean(st.frac_phase2_slots, data_axes),
        phase2_compacted=jax.lax.pmean(st.phase2_compacted, data_axes))
    return DistLDAState(topics=new_topics[None], D=D_new[None], W=W + dW,
                        key=key, iteration=iteration + 1), stats


def dp_programs(mesh: Mesh, plan: three_branch.Plan, alpha: float):
    """The data-parallel iteration's two programs over ``mesh``, as
    shard_maps to jit: ``_dp_sample`` (word, doc, mask, position and
    topic shards, D, Ŵ, key) and ``_dp_update`` (word, doc, mask, old and
    new topic shards, D, W, the sampler's stats, key, iteration)."""
    daxes = batch_axes(mesh)
    tok = P(daxes)
    st = DistLDAState(topics=tok, D=P(daxes, None, "model"),
                      W=P(None, "model"), key=P(), iteration=P())
    n_stats = len(three_branch.ThreeBranchStats._fields)
    shard_stats = three_branch.ThreeBranchStats(*[tok] * n_stats)
    sample = _shard_map(
        functools.partial(_dp_sample, plan=plan, alpha=alpha), mesh=mesh,
        in_specs=(tok,) * 5 + (st.D, st.W, st.key),
        out_specs=(tok, shard_stats, st.key), check_vma=False)
    update = _shard_map(
        functools.partial(_dp_update, data_axes=daxes), mesh=mesh,
        in_specs=(tok,) * 5 + (st.D, st.W, shard_stats, st.key,
                               st.iteration),
        out_specs=(st, three_branch.ThreeBranchStats(*[P()] * n_stats)),
        check_vma=False)
    return sample, update


def _shard_llpt(word_ids, doc_ids, mask, D, W, *, cfg: LDAConfig,
                data_axes: tuple[str, ...]):
    """LLPT of a dense state whose shards hold whole D rows and the whole
    W: each shard scores its own tokens (``core/llpt.py``'s per-token
    terms), and the masked sums meet in one psum."""
    ll = llpt_mod.token_ll(
        word_ids[0], doc_ids[0], D[0], W,
        jnp.sum(W, axis=0, dtype=jnp.float32), alpha=cfg.alpha_,
        beta=cfg.beta, n_words=W.shape[0], tile_size=cfg.tile_size)
    m = mask[0].astype(jnp.float32)
    return jax.lax.psum(jnp.sum(ll * m), data_axes) \
        / jax.lax.psum(jnp.sum(m), data_axes)


def _host_llpt(corpus: Corpus, cfg: LDAConfig, D, W) -> float:
    """LLPT of global host counts on the default device."""
    return float(llpt_mod.llpt(
        jnp.asarray(corpus.word_ids), jnp.asarray(corpus.doc_ids),
        jnp.ones(corpus.n_tokens, jnp.int32),
        jnp.asarray(D.astype(np.int32)), jnp.asarray(W.astype(np.int32)),
        alpha=cfg.alpha_, beta=cfg.beta, tile_size=cfg.tile_size))


# ---------------------------------------------------------------------------
# streamed residency (corpus_residency="streamed", DESIGN.md SS10)
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class _DistStream:
    """Per-device sub-shard extension of the device partition: each data
    shard's (N_loc,) token slice is tiled into ``n_sub`` equal column
    blocks of ``sub_len`` (extension slots carry mask 0 / the max word
    id, keeping every block word-sorted)."""
    n_sub: int
    sub_len: int
    n_loc: int                 # the resident per-device length (u length)
    word_ids: np.ndarray       # (S, n_sub·sub_len) int32
    doc_ids: np.ndarray        # (S, n_sub·sub_len) int32
    mask: np.ndarray           # (S, n_sub·sub_len) int32
    shared_slot: np.ndarray | None


def _extend_cols(arr: np.ndarray, total: int, fill) -> np.ndarray:
    out = np.full((arr.shape[0], total), fill, arr.dtype)
    out[:, :arr.shape[1]] = arr
    return out


class _StreamedDistMixin:
    """The streamed-residency half of DistLDATrainer.

    One epoch = one training iteration: every device streams its
    ``n_sub`` token sub-shards through the SAME per-token sweep the
    resident step runs (``_token_sweep``), against epoch-start counts
    and the epoch's word stats (``_word_phase``, computed once per epoch
    instead of once per iteration — same ops, same bits). The epoch's
    ±1 count moves accumulate in per-device delta matrices; the close
    applies them with the identical collectives the resident step uses
    per iteration (ΔW data-psum, shared-row psum under
    ``balance="tiles"``) — integer adds commute, so streamed == resident
    bit for bit (pinned by tests/test_streaming.py).
    """

    def _build_stream(self) -> None:
        from repro.train.lda_step import _Prefetcher
        sc = self.sc
        n_loc = int(sc.word_ids.shape[1])
        R = max(int(self.n_stream_shards), 2)
        L = -(-n_loc // R)
        total = R * L
        pad_word = self.corpus.n_words - 1
        self.stream = _DistStream(
            n_sub=R, sub_len=L, n_loc=n_loc,
            word_ids=_extend_cols(sc.word_ids, total, pad_word),
            doc_ids=_extend_cols(sc.doc_ids, total, 0),
            mask=_extend_cols(sc.mask, total, 0),
            shared_slot=None if sc.shared_slot is None else _extend_cols(
                sc.shared_slot, total,
                int(sc.shared_rows.shape[1])))
        self._prefetch = _Prefetcher(
            deadline_s=getattr(self.cfg, "stream_watchdog_seconds", None))
        self._stream_begin_fn = None
        self._stream_sub_fn = None
        self._stream_end_fn = None

    # -- sharding specs ------------------------------------------------------

    def _stream_specs(self):
        daxes = self.data_axes
        tok = P(daxes)
        mcol = None if self.layout is not None else "model"
        counts = (P(daxes, None, mcol), P(None, mcol)) \
            if self.layout is None else \
            (P(daxes, None, None), P(None, None),
             tuple(P(None, None) for _ in self.layout.tail_caps), P())
        derived = (P(None, mcol), P(None, None), P(None, None), P(None),
                   P(daxes, None))
        deltas = [P(daxes, None, mcol), P(daxes, None, mcol)]
        if self.stream.shared_slot is not None:
            deltas.append(P(daxes, None, mcol))
        return tok, counts, derived, tuple(deltas)

    # -- compiled epoch pieces ----------------------------------------------

    def _get_stream_begin(self):
        if self._stream_begin_fn is not None:
            return self._stream_begin_fn
        cfg, lay, g = self.cfg, self.layout, self.cfg.g
        n_words, m_loc = self.corpus.n_words, self.sc.m_local
        n_loc = self.stream.n_loc
        daxes = self.data_axes
        n_sh = 0 if self.stream.shared_slot is None \
            else int(self.sc.shared_rows.shape[1])
        tok, counts_s, derived_s, deltas_s = self._stream_specs()

        def begin(counts, key, iteration):
            if lay is None:
                D, W = counts
                Wl = W
                len_rows = jnp.sum(D[0], axis=-1, dtype=jnp.float32)
            else:
                d_packed, w_head, w_tail = counts[0][0], counts[1], counts[2]
                Wl = lay.densify_w(w_head, w_tail)
                len_rows = jnp.sum(sparse.unpack_pairs(d_packed)[1],
                                   axis=-1).astype(jnp.float32)
            k_local = Wl.shape[1]
            kb0 = jax.lax.axis_index("model") * k_local
            W_hat, g_vals, g_idx, q_prime = _word_phase(
                Wl, cfg=cfg, model_axis="model", n_words=n_words, g=g,
                kb0=kb0, k_local=k_local)
            len_tot = jax.lax.psum(len_rows, "model")
            # the epoch's per-device uniforms: the resident step's exact
            # key folding and (N_loc,) draw, staged to the host once per
            # epoch instead of regenerated per sub-shard
            k = jax.random.fold_in(key, iteration)
            for ax in daxes:
                k = jax.random.fold_in(k, jax.lax.axis_index(ax))
            u = jax.random.uniform(k, (n_loc,), dtype=jnp.float32)
            deltas = [jnp.zeros((m_loc, k_local), jnp.int32)[None],
                      jnp.zeros((n_words, k_local), jnp.int32)[None]]
            if n_sh:
                deltas.append(jnp.zeros((n_sh, k_local), jnp.int32)[None])
            return ((W_hat, g_vals, g_idx, q_prime, len_tot[None]),
                    tuple(deltas), u[None])

        sm = _shard_map(begin, mesh=self.mesh,
                        in_specs=(counts_s, P(), P()),
                        out_specs=(derived_s, deltas_s, tok),
                        check_vma=False)
        self._stream_begin_fn = jax.jit(sm)
        return self._stream_begin_fn

    def _get_stream_substep(self):
        if self._stream_sub_fn is not None:
            return self._stream_sub_fn
        cfg, lay, g = self.cfg, self.layout, self.cfg.g
        daxes = self.data_axes
        st = self.stream
        has_shared = st.shared_slot is not None
        tok, counts_s, derived_s, deltas_s = self._stream_specs()

        def substep(u_r, word_r, doc_r, mask_r, topics_r,
                    d_main, derived, deltas):
            u = u_r[0]
            word_r, doc_r, mask_r = word_r[0], doc_r[0], mask_r[0]
            if has_shared:
                ss_r = topics_r[1][0]
                topics = topics_r[0][0]
            else:
                topics = topics_r[0]
            W_hat, g_vals, g_idx, q_prime, len_tot = derived
            k_local = W_hat.shape[1]
            my = jax.lax.axis_index("model")
            kb0 = my * k_local
            if lay is None:
                d_rows = lambda d: d_main[0][d]           # noqa: E731
            else:
                d_rows = lambda d: sparse.densify_rows(   # noqa: E731
                    d_main[0][d], lay.n_topics)

            new_topics, skip, in_m, k1 = _token_sweep(
                u, word_r, doc_r, d_rows, len_tot[0], W_hat, g_vals,
                g_idx, q_prime, alpha=cfg.alpha_, g=g, kb0=kb0,
                k_local=k_local, my=my, model_axis="model",
                tile=cfg.tile_size)

            wgt = mask_r.astype(jnp.int32)

            def _blk(t):
                rel = t - kb0
                in_blk = (rel >= 0) & (rel < k_local)
                return jnp.clip(rel, 0, k_local - 1), \
                    jnp.where(in_blk, wgt, 0)

            old_rel, w_old = _blk(topics)
            t_rel, w_new = _blk(new_topics)
            with jax.named_scope("lda.count_update"):
                dD = deltas[0][0].at[doc_r, old_rel].add(-w_old) \
                                 .at[doc_r, t_rel].add(w_new)
                dW = deltas[1][0].at[word_r, old_rel].add(-w_old) \
                                 .at[word_r, t_rel].add(w_new)
            out_deltas = [dD[None], dW[None]]
            if has_shared:
                n_sh = deltas[2].shape[1]
                dsh = jnp.zeros((n_sh + 1, k_local), jnp.int32) \
                    .at[ss_r, old_rel].add(-w_old) \
                    .at[ss_r, t_rel].add(w_new)[:n_sh]
                out_deltas.append((deltas[2][0] + dsh)[None])

            fmask = mask_r.astype(jnp.float32)
            def _tot(v):
                return jax.lax.psum(jnp.sum(v * fmask), daxes)
            sums = jnp.stack([
                _tot(skip.astype(jnp.float32)),
                _tot((skip | in_m).astype(jnp.float32)),
                _tot((new_topics == topics).astype(jnp.float32)),
                _tot((new_topics == k1).astype(jnp.float32)),
                _tot(jnp.ones_like(fmask))])      # every token is drawn
            n_surv = _tot((~skip).astype(jnp.float32))
            return new_topics[None], tuple(out_deltas), n_surv, sums

        topics_spec = (tok, tok) if has_shared else tok
        sm = _shard_map(
            substep, mesh=self.mesh,
            in_specs=(tok, tok, tok, tok, topics_spec,
                      counts_s[0], derived_s, deltas_s),
            out_specs=(tok, deltas_s, P(), P()), check_vma=False)
        # donate the topics buffer (reused by the returned topics) and
        # the accumulated deltas
        self._stream_sub_fn = jax.jit(sm, donate_argnums=(4, 7))
        return self._stream_sub_fn

    def _get_stream_end(self):
        if self._stream_end_fn is not None:
            return self._stream_end_fn
        cfg, lay = self.cfg, self.layout
        daxes = self.data_axes
        has_shared = self.stream.shared_slot is not None
        tok, counts_s, derived_s, deltas_s = self._stream_specs()

        def end(counts, deltas, *shared_rows):
            with jax.named_scope("lda.w_psum"):
                dW_tot = jax.lax.psum(deltas[1][0], daxes)
            if lay is None:
                D, W = counts
                D_new = D[0] + deltas[0][0]
                if has_shared:
                    dsh = deltas[2][0]
                    remote = jax.lax.psum(dsh, daxes) - dsh
                    D_new = D_new.at[shared_rows[0][0]].add(remote,
                                                            mode="drop")
                return (D_new[None], W + dW_tot)
            d_packed, w_head, w_tail, overflow = counts
            d_dense = sparse.densify_rows(d_packed[0], lay.n_topics)
            d_new = d_dense + deltas[0][0]
            d_repacked, ov = sparse.pack_rows_sorted(d_new, lay.d_capacity)
            overflow = overflow + jax.lax.psum(ov, daxes)
            w_full = lay.densify_w(w_head, w_tail) + dW_tot
            w_head_new, w_tail_new = lay.split_w(w_full)
            return (d_repacked[None], w_head_new, w_tail_new, overflow)

        in_specs = (counts_s, deltas_s) + \
            ((P(daxes, None),) if has_shared else ())
        sm = _shard_map(end, mesh=self.mesh, in_specs=in_specs,
                        out_specs=counts_s, check_vma=False)
        # counts alias the outputs; the deltas drop with the epoch carry
        self._stream_end_fn = jax.jit(sm, donate_argnums=(0,))
        return self._stream_end_fn

    # -- the epoch loop ------------------------------------------------------

    def _put_substream(self, r: int, host_topics: np.ndarray,
                       u_host: np.ndarray):
        if chaos.armed():
            chaos.io_fault(r)
        st = self.stream
        cols = slice(r * st.sub_len, (r + 1) * st.sub_len)
        dev = NamedSharding(self.mesh, P(self.data_axes))
        # host arrays go straight to the sharded layout — routing through
        # jnp.asarray first would commit them to device 0 and re-shard
        put = lambda a: jax.device_put(np.ascontiguousarray(a), dev)
        topics = put(host_topics[:, cols])
        if st.shared_slot is not None:
            topics = (topics, put(st.shared_slot[:, cols]))
        return (put(u_host[:, cols]), put(st.word_ids[:, cols]),
                put(st.doc_ids[:, cols]), put(st.mask[:, cols]), topics)

    def _stream_epoch(self, ss: DistStreamState) -> DistStreamState:
        st = self.stream
        if ss.epoch is None:
            derived, deltas, u_dev = self._get_stream_begin()(
                ss.counts, ss.key, jnp.int32(ss.iteration))
            u_host = np.zeros((self.sc.n_shards, st.n_sub * st.sub_len),
                              np.float32)
            u_host[:, :st.n_loc] = np.asarray(u_dev)
            ss.epoch = _DistEpochCarry(derived=derived, deltas=deltas,
                                       u_host=u_host)
        ep = ss.epoch
        sub = self._get_stream_substep()
        d_main = ss.counts[0]
        self._prefetch.take()
        current = self._put_substream(ss.cursor, ss.host_topics, ep.u_host)
        pending = []                # one-deep deferred D2H (no bubbles)
        while ss.cursor < st.n_sub:
            r = ss.cursor
            if chaos.armed():
                chaos.shard_event(ss.iteration, r)
            if r + 1 < st.n_sub:
                self._prefetch.submit(self._put_substream, r + 1,
                                      ss.host_topics, ep.u_host)
            u_r, word_r, doc_r, mask_r, topics_r = current
            new_t, ep.deltas, n_surv, sums = sub(
                u_r, word_r, doc_r, mask_r, topics_r, d_main,
                ep.derived, ep.deltas)
            ep.stats_parts.append((n_surv, sums))
            pending.append((r, new_t))
            if len(pending) > 1:
                r_prev, t_prev = pending.pop(0)
                cols = slice(r_prev * st.sub_len, (r_prev + 1) * st.sub_len)
                ss.host_topics[:, cols] = np.asarray(t_prev)
            ss.cursor += 1
            current = self._prefetch.take()
        for r_prev, t_prev in pending:
            cols = slice(r_prev * st.sub_len, (r_prev + 1) * st.sub_len)
            ss.host_topics[:, cols] = np.asarray(t_prev)
        for n_surv, sums in ep.stats_parts:
            ep.n_surv += float(n_surv)
            ep.stat_sums += np.asarray(sums, np.float64)
        ep.stats_parts = []
        n_surv_total, sums_total = ep.n_surv, ep.stat_sums
        end = self._get_stream_end()
        extra = (self.shared_rows,) if st.shared_slot is not None else ()
        ss.counts = end(ss.counts, ep.deltas, *extra)
        ss.iteration += 1
        ss.cursor = 0
        ss.epoch = None
        return ss, n_surv_total, sums_total

    def _stream_run(self, ss: DistStreamState, n_iters: int):
        denom = float(max(int(self.sc.mask.sum()), 1))
        rows = []
        for _ in range(int(n_iters)):
            ss, _n_surv, sums = self._stream_epoch(ss)
            rows.append(sums / denom)
        m = np.asarray(rows, np.float32).reshape(-1, 5)
        stats = three_branch.ThreeBranchStats(
            frac_skipped=m[:, 0], frac_m_final=m[:, 1],
            frac_unchanged=m[:, 2], frac_at_max=m[:, 3],
            frac_q_branch=np.zeros(len(rows), np.float32),
            frac_phase2_slots=m[:, 4])
        return ss, stats


# ---------------------------------------------------------------------------
# driver
# ---------------------------------------------------------------------------

def _host_counts(sc: ShardedCorpus, corpus: Corpus, n_topics: int,
                 t_np: np.ndarray):
    """(D, W) host count matrices from per-shard topics (see
    DistLDATrainer._build_counts for the replication semantics)."""
    S, K = sc.n_shards, n_topics
    sel = sc.mask > 0
    gdoc = np.take_along_axis(sc.doc_map, sc.doc_ids.astype(np.int64),
                              axis=1)[sel]
    t = np.asarray(t_np)[sel].astype(np.int64)
    # one histogram pass each (np.add.at takes ~10x as long at 46 M tokens)
    Dg = np.bincount(gdoc * K + t, minlength=corpus.n_docs * K) \
        .reshape(corpus.n_docs, K)
    W = np.bincount(sc.word_ids[sel].astype(np.int64) * K + t,
                    minlength=corpus.n_words * K) \
        .reshape(corpus.n_words, K).astype(np.int32)
    D = np.zeros((S, sc.m_local, K), np.int32)
    for s in range(S):
        nd = int(sc.docs_per_shard[s])
        D[s, :nd] = Dg[sc.doc_map[s][:nd]]
    return D, W


class DistLDATrainer(_StreamedDistMixin):
    """shard_map-based multi-device EZLDA trainer.

    mesh must carry a 'model' axis (size 1 reproduces the paper's pure
    data-parallel scheme) plus 'data' (and optionally 'pod') axes.
    K must divide the model-axis size; data shards = data-axis extent.

    Engine-internal: this is the ``backend="distributed"`` backend of
    ``repro.lda.api.LDAEngine`` (with ``dist.w_sync="replicate"``), which
    owns mesh defaulting, the unified checkpoint format, and the serving
    export. Direct construction raises TypeError (it warned for one
    release; the engine is the only front door now).
    """

    def __init__(self, corpus: Corpus, config: LDAConfig, mesh: Mesh,
                 pad_multiple: int = 1024, *, _from_engine: bool = False):
        if not _from_engine:
            raise TypeError(
                "DistLDATrainer is an engine-internal backend: construct "
                "through repro.lda.api.LDAEngine(corpus, config, "
                "backend='distributed') — it wraps this trainer with "
                "unified checkpoints and the serving export path")
        if "model" not in mesh.shape:
            raise ValueError(
                f"mesh axes {tuple(mesh.shape)} lack a 'model' axis: the "
                "distributed trainer needs one (size 1 reproduces the "
                "paper's pure data-parallel scheme)")
        if config.sampler == "warp":
            raise ValueError(
                "sampler='warp' is single-backend only in this release: "
                "the MH doc proposal gathers topics of arbitrary same-doc "
                "tokens, and dissected documents would need remote topic "
                "gathers every proposal cycle. Use backend='single' for "
                "the warp engine, or sampler='three_branch' on this "
                "distributed trainer")
        self.cfg = config
        self.mesh = mesh
        self.data_axes = batch_axes(mesh)
        self.pm = mesh.shape["model"]
        if config.n_topics % self.pm != 0:
            raise ValueError(
                f"n_topics={config.n_topics} is not divisible by the model "
                f"mesh axis ({self.pm}): topic-axis model parallelism "
                "block-partitions K over the model shards")
        self.layout = None
        if config.format == "hybrid":
            if self.pm != 1:
                raise ValueError(
                    "format='hybrid' needs a model mesh axis of size 1: "
                    "packed ELL slots store GLOBAL topic ids, which do not "
                    "block-partition over the topic axis. Use a pure "
                    "data-parallel mesh (the paper's §V-B scheme) or "
                    "format='dense' for topic-axis model parallelism")
            if config.balance == "tiles":
                raise ValueError(
                    "balance='tiles' with format='hybrid' is not supported "
                    "on the distributed backend: dissected documents need "
                    "remote dense D-row deltas, which packed ELL rows "
                    "cannot absorb scatter-free. Use format='dense' for "
                    "token-balanced sharding, or balance='none' (document "
                    "chunking) with the hybrid state")
            self.layout = HybridLayout.build(corpus, config)
        n_data = int(np.prod([mesh.shape[a] for a in self.data_axes]))
        self.sc = shard_corpus(corpus, n_data, pad_multiple,
                               balance=config.balance)
        self.corpus = corpus

        daxes = self.data_axes
        tok_spec = P(daxes)
        if self.layout is None:
            self.state_specs = DistLDAState(
                topics=tok_spec,
                D=P(daxes, None, "model"),
                W=P(None, "model"),
                key=P(), iteration=P())
        else:
            self.state_specs = DistHybridState(
                topics=tok_spec,
                D=P(daxes, None, None),
                W_head=P(None, None),
                W_tail=tuple(P(None, None) for _ in self.layout.tail_caps),
                overflow=P(), key=P(), iteration=P())
        stats_spec = three_branch.ThreeBranchStats(
            *[P()] * len(three_branch.ThreeBranchStats._fields))
        from repro.train.lda_step import resolve_residency
        self.residency, self.n_stream_shards = resolve_residency(
            config, int(self.sc.word_ids.shape[1]))
        # the resident dense data-parallel path draws the single backend's
        # topics (_dp_sample, _dp_update); topic blocks, dissected
        # documents, packed D rows and streamed residency (out-of-core:
        # token arrays stay HOST-side and each device streams its own
        # sub-shard sequence, DESIGN.md SS10) keep the per-shard sweep and
        # its own draw (_dist_step)
        self.draws_as_single = (self.layout is None and self.pm == 1
                                and self.sc.shared_slot is None
                                and self.residency != "streamed")
        step = functools.partial(
            _dist_step, cfg=config, data_axes=daxes, model_axis="model",
            n_words=corpus.n_words, m_local=self.sc.m_local, g=config.g,
            layout=self.layout)
        if self.draws_as_single:
            self.plan = three_branch.plan_for(
                int(self.sc.word_ids.shape[1]), config)
            sm_sample, sm_update = dp_programs(mesh, self.plan,
                                               config.alpha_)
            self._sample = jax.jit(sm_sample)
            self._update = jax.jit(sm_update)
            # run_fused's update: the new topics, D and W donated
            self._run_update = jax.jit(sm_update, donate_argnums=(4, 5, 6))
        elif self.sc.shared_slot is not None:
            def step_shared(word_ids, doc_ids, mask, shared_slot,
                            shared_rows, state):
                return step(word_ids, doc_ids, mask, state,
                            shared=(shared_slot, shared_rows))
            self._sm_step = _shard_map(
                step_shared, mesh=mesh,
                in_specs=(tok_spec, tok_spec, tok_spec, tok_spec,
                          P(daxes, None), self.state_specs),
                out_specs=(self.state_specs, stats_spec),
                check_vma=False)
        else:
            self._sm_step = _shard_map(
                step, mesh=mesh,
                in_specs=(tok_spec, tok_spec, tok_spec, self.state_specs),
                out_specs=(self.state_specs, stats_spec),
                check_vma=False)
        if not self.draws_as_single:
            self._step = jax.jit(self._sm_step)
        self._llpt = None
        if self.layout is None and self.pm == 1:
            # every shard holds whole D rows (a dissected document's row
            # in full on each holder) and the whole W
            self._llpt = jax.jit(_shard_map(
                functools.partial(_shard_llpt, cfg=config, data_axes=daxes),
                mesh=mesh,
                in_specs=(tok_spec,) * 3 + (P(daxes, None, None), P()),
                out_specs=P(), check_vma=False))

        dev = NamedSharding(mesh, tok_spec)
        if self.residency == "streamed":
            self._build_stream()
            self._step_inputs = None
            if self.sc.shared_rows is not None:
                self.shared_rows = jax.device_put(
                    jnp.asarray(self.sc.shared_rows),
                    NamedSharding(mesh, P(daxes, None)))
            return
        self.word_ids = jax.device_put(jnp.asarray(self.sc.word_ids), dev)
        self.doc_ids = jax.device_put(jnp.asarray(self.sc.doc_ids), dev)
        self.mask = jax.device_put(jnp.asarray(self.sc.mask), dev)
        if self.draws_as_single:
            # each slot's global token position: its uniform's counter
            self.global_pos = jax.device_put(
                self.sc.global_pos.astype(np.uint32), dev)
            self._step_inputs = (self.word_ids, self.doc_ids, self.mask,
                                 self.global_pos)
        elif self.sc.shared_slot is not None:
            self.shared_slot = jax.device_put(
                jnp.asarray(self.sc.shared_slot), dev)
            self.shared_rows = jax.device_put(
                jnp.asarray(self.sc.shared_rows),
                NamedSharding(mesh, P(daxes, None)))
            self._step_inputs = (self.word_ids, self.doc_ids, self.mask,
                                 self.shared_slot, self.shared_rows)
        else:
            self._step_inputs = (self.word_ids, self.doc_ids, self.mask)
        if not self.draws_as_single:
            # run_fused's step: the same program, the state's buffers donated
            self._run_step = jax.jit(self._sm_step,
                                     donate_argnums=(len(self._step_inputs),))

    def _put(self, x, spec):
        return jax.device_put(jnp.asarray(x), NamedSharding(self.mesh, spec))

    def _device_counts(self, D, W) -> tuple:
        """Place dense host count matrices as the configured format's
        device-resident count tuple (the streamed state's ``counts``)."""
        put = self._put
        if self.layout is None:
            return (put(D, P(self.data_axes, None, "model")),
                    put(W, P(None, "model")))
        lay = self.layout
        s_n, m_loc = self.sc.n_shards, self.sc.m_local
        d_flat = jnp.asarray(np.asarray(D).reshape(s_n * m_loc, -1))
        d_packed = sparse.build_sparse_rows(d_flat, lay.d_capacity) \
            .reshape(s_n, m_loc, lay.d_capacity)
        w_head, w_tail = lay.split_w(jnp.asarray(W))
        return (put(d_packed, P(self.data_axes, None, None)),
                put(w_head, P(None, None)),
                tuple(put(b, P(None, None)) for b in w_tail),
                put(jnp.int32(0), P()))

    def _device_state(self, topics, D, W, key, iteration):
        """Place (dense host counts, topics) as the configured state format.

        The key and the iteration go to the mesh as the step returns them,
        so the first call of the step compiles the program every later
        call runs."""
        counts = self._device_counts(D, W)
        topics = self._put(topics, P(self.data_axes))
        rep = NamedSharding(self.mesh, P())
        key = jax.device_put(key, rep)
        iteration = jax.device_put(jnp.asarray(iteration, jnp.int32), rep)
        if self.layout is None:
            return DistLDAState(topics=topics, D=counts[0], W=counts[1],
                                key=key, iteration=iteration)
        return DistHybridState(
            topics=topics, D=counts[0], W_head=counts[1],
            W_tail=counts[2], overflow=counts[3],
            key=key, iteration=iteration)

    def _build_counts(self, t_np: np.ndarray):
        """(D, W) host counts from per-shard topics.

        D rows are built from the GLOBAL per-document histogram and placed
        on every shard holding the doc — identical to the shard-local
        histogram under document chunking (each doc is whole on one
        shard), and the required full-row replica for docs dissected
        across shards under balance="tiles".
        """
        return _host_counts(self.sc, self.corpus, self.cfg.n_topics, t_np)

    def init_state(self):
        cfg = self.cfg
        key = jax.random.PRNGKey(cfg.seed)
        if self.draws_as_single:
            # the single backend's initial draw (one split, one randint
            # in global token order), so one seed trains one chain on
            # either backend
            key, sub = jax.random.split(key)
            return self.state_from_payload({
                "topics_global": np.asarray(jax.random.randint(
                    sub, (self.corpus.n_tokens,), 0, cfg.n_topics,
                    dtype=jnp.int32)),
                "key": np.asarray(jax.random.key_data(key)),
                "iteration": 0})
        # the SAME initial draw as the resident path (bit-for-bit), even
        # when the topics then live host-side for streaming
        topics = jax.random.randint(
            jax.random.fold_in(key, 7), self.sc.word_ids.shape, 0,
            cfg.n_topics, dtype=jnp.int32)
        D, W = self._build_counts(np.asarray(topics))
        if self.residency == "streamed":
            return self._stream_state(np.asarray(topics), D, W, key, 0)
        return self._device_state(topics, D, W, key, jnp.int32(0))

    def _stream_state(self, topics_nloc: np.ndarray, D, W, key,
                      iteration: int) -> DistStreamState:
        st = self.stream
        host = _extend_cols(np.asarray(topics_nloc, np.int32),
                            st.n_sub * st.sub_len, 0)
        return DistStreamState(host_topics=host,
                               counts=self._device_counts(D, W),
                               key=key, iteration=int(iteration))

    def step(self, state):
        if isinstance(state, DistStreamState):
            raise ValueError(
                "a streamed distributed trainer advances by whole epochs "
                "(every token sub-shard must stream through before the "
                "counts apply): use run_fused(state, n_iters)")
        if self.draws_as_single:
            return self._dp_iteration(state, self._update)
        return self._step(*self._step_inputs, state)

    def _dp_iteration(self, state: DistLDAState, update):
        """One data-parallel iteration as the single backend splits it:
        Ŵ from the replicated W, the shards' draw (``jit__dp_sample``),
        then ``update`` (``jit__dp_update``: the counts, the W-delta
        all-reduce and the stats), with no host sync between them."""
        W_hat = esca.compute_w_hat(state.W, self.cfg.beta)
        new_topics, st, key = self._sample(
            *self._step_inputs, state.topics, state.D, W_hat, state.key)
        return update(*self._step_inputs[:3], state.topics, new_topics,
                      state.D, state.W, st, key, state.iteration)

    def run_fused(self, state: DistLDAState, n_iters: int):
        """n_iters eval-free iterations, the state's buffers donated.

        One program for every ``n_iters``: the one-iteration step is
        dispatched ``n_iters`` times without a host sync between them (a
        scan per length would compile again for each new ``fit(n)``).
        Returns (state, stacked stats) where each stats leaf has a leading
        (n_iters,) axis.
        """
        if chaos.armed():
            # host-level chaos surface for the traced _dist_step: the int()
            # sync only happens with a plan armed, never in production
            chaos.step_range(int(state.iteration), int(n_iters))
        if isinstance(state, DistStreamState):
            return self._stream_run(state, n_iters)
        rows = []
        for _ in range(int(n_iters)):
            if self.draws_as_single:
                state, stats = self._dp_iteration(state, self._run_update)
            else:
                state, stats = self._run_step(*self._step_inputs, state)
            rows.append(stats)
        return state, three_branch.ThreeBranchStats(*[
            np.asarray([np.asarray(getattr(r, f)) for r in rows], np.float32)
            for f in three_branch.ThreeBranchStats._fields])

    # -- elastic checkpointing ---------------------------------------------
    # Checkpoints store topics in GLOBAL token order (+ rng + iteration), so
    # a restore can target a mesh with a different data extent: counts are
    # derived state and get rebuilt for whatever chunking the new trainer
    # uses (DESIGN.md §6 "elastic restore").

    def host_payload(self, state) -> dict:
        if isinstance(state, DistStreamState):
            if state.cursor:
                raise ValueError(
                    "streamed distributed states checkpoint at epoch "
                    f"boundaries only, but {state.cursor} sub-shards of "
                    "the open epoch are sampled: finish the epoch "
                    "(run_fused) first. Mid-epoch restore is a single-"
                    "host streaming feature (docs/API.md)")
            t = state.host_topics[:, :self.stream.n_loc]
        else:
            t = np.asarray(state.topics)
        out = np.zeros(self.corpus.n_tokens, np.int32)
        for s in range(self.sc.n_shards):
            sel = self.sc.mask[s] > 0
            out[self.sc.global_pos[s][sel]] = t[s][sel]
        return {"topics_global": out,
                "key": np.asarray(jax.random.key_data(state.key)),
                "iteration": int(state.iteration)}

    def state_from_payload(self, payload: dict):
        if int(np.asarray(payload.get("stream_cursor", 0))) > 0:
            raise ValueError(
                "mid-epoch streaming checkpoints restore on the single-"
                "host backend only; this distributed trainer needs an "
                "epoch-boundary payload (no stream_cursor)")
        tg = np.asarray(payload["topics_global"], np.int32)
        if tg.shape[0] != self.corpus.n_tokens:
            raise ValueError(
                f"checkpoint topics_global has {tg.shape[0]} entries but "
                f"the corpus holds {self.corpus.n_tokens} tokens: the "
                "checkpoint belongs to a different corpus")
        S = self.sc.n_shards
        topics = np.zeros_like(self.sc.word_ids)
        for s in range(S):
            sel = self.sc.mask[s] > 0
            topics[s][sel] = tg[self.sc.global_pos[s][sel]]
        D, W = self._build_counts(topics)
        key = jax.random.wrap_key_data(jnp.asarray(payload["key"]))
        if self.residency == "streamed":
            return self._stream_state(topics, D, W, key,
                                      int(payload["iteration"]))
        return self._device_state(topics, D, W, key,
                                  jnp.int32(payload["iteration"]))

    def _counts_view(self, state):
        """Adapter: a .D/.W(-parts) view over either state flavor."""
        if not isinstance(state, DistStreamState):
            return state
        import types
        if self.layout is None:
            return types.SimpleNamespace(D=state.counts[0],
                                         W=state.counts[1])
        return types.SimpleNamespace(D=state.counts[0],
                                     W_head=state.counts[1],
                                     W_tail=state.counts[2])

    def state_nbytes(self, state) -> int:
        """Measured live count-state bytes (all shards' D + the W replica)."""
        state = self._counts_view(state)
        if self.layout is None:
            return int(state.D.size + state.W.size) * 4
        total = int(state.D.size + state.W_head.size)
        total += sum(int(b.size) for b in state.W_tail)
        return total * 4

    def gather_global(self, state):
        """Global (D, W) count matrices for eval/parity checks."""
        state = self._counts_view(state)
        if self.layout is None:
            W = np.asarray(state.W)
            D_sh = np.asarray(state.D)
        else:
            lay = self.layout
            W = np.asarray(lay.densify_w(state.W_head, state.W_tail))
            s_n, m_loc = self.sc.n_shards, self.sc.m_local
            # host copy first: the sharded D must not meet densify's
            # replicated operands in eager code (an Explicit-axes mesh
            # rejects mixed shardings)
            flat = jnp.asarray(np.asarray(state.D).reshape(s_n * m_loc, -1))
            D_sh = np.asarray(sparse.densify_rows(flat, lay.n_topics)) \
                .reshape(s_n, m_loc, lay.n_topics)
        K = W.shape[1]
        D = np.zeros((self.corpus.n_docs, K), np.int64)
        for s in range(self.sc.n_shards):
            nd = int(self.sc.docs_per_shard[s])
            rows = self.sc.doc_map[s][:nd]
            d_rows = D_sh[s][:nd]
            if self.sc.owns is not None:
                # dissected docs hold FULL replicas on every shard — count
                # each doc once, through its gather owner
                sel = self.sc.owns[s][:nd] > 0
                rows, d_rows = rows[sel], d_rows[sel]
            D[rows] += d_rows
        return D, W

    def selfcheck(self, state) -> None:
        """Count-invariant tripwire on the gathered global counts
        (``config.selfcheck``; called at chunk boundaries by the engine's
        distributed backend — a gather per boundary, not per step)."""
        D, W = self.gather_global(state)
        invariants.check_dense_counts(
            D, W, n_tokens=self.corpus.n_tokens,
            where=f"distributed chunk boundary (iteration "
                  f"{int(state.iteration)})")

    def llpt(self, state) -> float:
        """LLPT of the state. A resident dense state with whole topics on
        each shard is scored on the chips (program ``jit__shard_llpt``):
        each shard's tokens against its D rows and its W replica, one
        psum. Any other state is gathered to the host first."""
        if self._llpt is None or isinstance(state, DistStreamState):
            D, W = self.gather_global(state)
            return _host_llpt(self.corpus, self.cfg, D, W)
        return float(self._llpt(self.word_ids, self.doc_ids, self.mask,
                                state.D, state.W))


# ---------------------------------------------------------------------------
# parameter-server w_sync (config.dist.w_sync == "ps", DESIGN.md §15)
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class _PSEpochCarry:
    """One worker's open-round state: the epoch uniforms (host-staged),
    the epoch-start word stats inputs (global colsum pulled once from the
    server), the accumulated device D delta, and the epoch-start topics
    (the canonical cut a mid-epoch checkpoint restores from)."""
    u_host: np.ndarray             # (R·L,) f32
    len_tot: jax.Array             # (M_loc,) f32 — epoch-start doc lengths
    colsum: jax.Array              # (K,) f32 — exact int colsum from server
    dD: jax.Array                  # (M_loc, K) int32 accumulator
    start_topics: np.ndarray       # (R·L,) int32 epoch-start copy
    n_surv: float = 0.0
    stat_sums: np.ndarray = dataclasses.field(
        default_factory=lambda: np.zeros(5, np.float64))


@dataclasses.dataclass
class PSStreamState:
    """Training state under ``w_sync="ps"``: token topics host-staged per
    worker, per-worker device D blocks, and W living ONLY in the
    word-sharded parameter server (``repro.lda.ps``) — no worker ever
    holds more than one page of W rows.

    ``clocks[w]`` counts rounds (epochs) worker ``w`` has finished; the
    state's ``iteration`` is the slowest worker's clock, which equals the
    server's committed round.
    """
    host_topics: np.ndarray        # (S, R·L) int32
    d_blocks: list                 # per-worker (M_loc, K) dense or (M_loc, L) packed
    server: Any                    # ps.ParameterServer (owns committed W)
    clients: list                  # ps.PSClient per worker (owns the journal)
    key: jax.Array
    clocks: np.ndarray             # (S,) int64 — rounds finished per worker
    cursors: np.ndarray            # (S,) int64 — sub-shard cursor of open round
    epochs: list                   # per-worker _PSEpochCarry | None
    overflow: int = 0              # hybrid repack drop tripwire (global)
    stat_rounds: dict = dataclasses.field(default_factory=dict)

    @property
    def iteration(self) -> int:
        return int(self.clocks.min())

    @property
    def topics(self) -> np.ndarray:
        return self.host_topics


class PSDistTrainer:
    """Word-sharded parameter-server EZLDA trainer (``w_sync="ps"``).

    Same corpus chunking and per-token math as ``DistLDATrainer``, but W
    is never replicated: ``repro.lda.ps.ParameterServer`` owns contiguous
    word-range shards, each worker pulls only the page of rows its
    current token sub-shard touches (plus the global per-topic column
    sum), pushes int32 delta blocks back, and a stale-synchronous clock
    (``config.dist.staleness``) bounds worker skew.

    Bitwise parity at ``staleness=0`` is by construction, not by luck:
    each worker's sweep runs the SAME ``_word_phase`` / ``_token_sweep``
    the replicated path runs, inside a shard_map over a trivial
    one-device mesh (size-1 collectives are identities), with the worker's
    mesh coordinates folded into the key exactly as the replicated step
    folds ``axis_index``; and the server's round-commit rule (a round
    applies only when EVERY worker finished it) means a round-``c`` pull
    observes precisely the state the §V-B sum+broadcast would have
    delivered. Pinned by tests/test_ps.py. Restrictions: model mesh axis
    must be size 1 (pages are row windows; topic-block sharding of a
    window recreates the replication PS removes) and
    ``balance="none"`` (tiles' shared-row psum couples shards within an
    iteration, which contradicts independent worker progress).

    Mid-epoch checkpoints (the distributed carry-over): ``host_payload``
    on a state with open rounds emits the canonical epoch-start topics
    (the consistent cut) plus ``ps_*`` extension keys — per-worker delta
    cursors, done-sub-shard topics, and the per-owner committed W row
    blocks. Restores rebuild the open rounds' device deltas and re-queue
    the partial-round pushes from the done topics (counts are derived
    state), so recovery replays unacked pushes without a wire log.
    """

    def __init__(self, corpus: Corpus, config: LDAConfig, mesh: Mesh,
                 pad_multiple: int = 1024, *, _from_engine: bool = False):
        from repro.lda import ps as ps_mod
        if not _from_engine:
            raise TypeError(
                "PSDistTrainer is an engine-internal backend: construct "
                "through repro.lda.api.LDAEngine with "
                "LDAConfig(dist=DistConfig(w_sync='ps', ...))")
        if "model" not in mesh.shape:
            raise ValueError(
                f"mesh axes {tuple(mesh.shape)} lack a 'model' axis")
        if mesh.shape["model"] != 1:
            raise ValueError(
                "w_sync='ps' needs a model mesh axis of size 1: W pages "
                "are row windows of the global matrix, and topic-block "
                "sharding a window would re-replicate the columns the "
                "parameter server exists to shard. Use topic-axis model "
                "parallelism with w_sync='replicate'")
        if config.balance != "none":
            raise ValueError(
                "w_sync='ps' requires balance='none': tiles replicate "
                "dissected documents' D rows and glue them with a "
                "per-iteration cross-shard psum, which contradicts "
                "independent worker progress under a staleness bound")
        if config.sampler == "warp":
            raise ValueError(
                "sampler='warp' is single-backend only (see "
                "DistLDATrainer); w_sync='ps' uses the three-branch sweep")
        if config.corpus_residency == "disk" or (
                config.corpus_residency == "auto"
                and config.corpus_path is not None):
            raise ValueError(
                "w_sync='ps' streams host-staged token shards; the "
                "disk-native corpus store is not yet plumbed through the "
                "PS epoch loop (use w_sync='replicate' for "
                "corpus_residency='disk')")
        self.cfg = config
        self.dist_cfg = config.dist
        self.mesh = mesh
        self.corpus = corpus
        self.data_axes = batch_axes(mesh)
        S = int(np.prod([mesh.shape[a] for a in self.data_axes]))
        self.sc = shard_corpus(corpus, S, pad_multiple, balance="none")
        self.layout = None
        if config.format == "hybrid":
            self.layout = HybridLayout.build(corpus, config)

        # -- sub-shard geometry (the _DistStream tiling, host-side) --------
        from repro.train.lda_step import resolve_residency
        self.residency, n_stream = resolve_residency(
            config, int(self.sc.word_ids.shape[1]))
        n_loc = int(self.sc.word_ids.shape[1])
        R = max(int(n_stream), 2) if self.residency == "streamed" \
            else max(int(config.stream_shards or 4), 2)
        L = -(-n_loc // R)
        total = R * L
        V = corpus.n_words
        pad_word = V - 1
        self._R, self._L, self._n_loc = R, L, n_loc
        self._st_word = _extend_cols(self.sc.word_ids, total, pad_word)
        self._st_doc = _extend_cols(self.sc.doc_ids, total, 0)
        self._st_mask = _extend_cols(self.sc.mask, total, 0)

        # per-(worker, sub-shard) word runs → one uniform page geometry:
        # the page is the max run span so a single compiled sub fn serves
        # every (worker, sub-shard) pair; bases clamp into [0, V - P]
        spans = np.ones((S, R), np.int64)
        lows = np.zeros((S, R), np.int64)
        for w in range(S):
            for r in range(R):
                cols = slice(r * L, (r + 1) * L)
                m = self._st_mask[w, cols] > 0
                if m.any():
                    wr = self._st_word[w, cols][m]
                    lows[w, r] = int(wr[0])          # word-sorted blocks
                    spans[w, r] = int(wr[-1]) - int(wr[0]) + 1
        P_rows = int(min(max(int(spans.max()), 1), V))
        self._page_rows = P_rows
        self._bases = np.minimum(lows, V - P_rows).astype(np.int64)
        self._word_rel = np.empty_like(self._st_word)
        for w in range(S):
            for r in range(R):
                cols = slice(r * L, (r + 1) * L)
                self._word_rel[w, cols] = np.clip(
                    self._st_word[w, cols] - self._bases[w, r],
                    0, P_rows - 1).astype(np.int32)

        # -- ownership --------------------------------------------------------
        dc = self.dist_cfg
        n_owners = dc.n_owners if dc.n_owners is not None else S
        row_mass = None
        if dc.owner_layout == "mass":
            row_mass = np.bincount(corpus.word_ids, minlength=V)
        self.owner_layout = ps_mod.OwnerLayout.build(
            V, n_owners, layout=dc.owner_layout, row_mass=row_mass)
        self._ps_mod = ps_mod

        # -- the trivial one-device mesh the per-worker sweeps run under ----
        dev0 = np.asarray(mesh.devices).reshape(-1)[:1].reshape(1, 1)
        self._tmesh = Mesh(dev0, ("data", "model"))
        self._coords = [
            jnp.asarray(np.unravel_index(
                w, [mesh.shape[a] for a in self.data_axes]), jnp.int32)
            for w in range(S)]
        self._begin_fn = None
        self._sub_fn = None
        self._close_fn = None

    # -- compiled per-worker pieces -----------------------------------------

    def _get_begin(self):
        if self._begin_fn is not None:
            return self._begin_fn
        lay, n_loc, n_daxes = self.layout, self._n_loc, len(self.data_axes)

        def begin(d_block, key, iteration, coords):
            if lay is None:
                len_rows = jnp.sum(d_block, axis=-1, dtype=jnp.float32)
            else:
                len_rows = jnp.sum(sparse.unpack_pairs(d_block)[1],
                                   axis=-1).astype(jnp.float32)
            len_tot = jax.lax.psum(len_rows, "model")
            # the replicated begin's exact key discipline: fold the
            # iteration, then this worker's coordinate along each data
            # axis (axis_index over there == unravel_index here)
            k = jax.random.fold_in(key, iteration)
            for i in range(n_daxes):
                k = jax.random.fold_in(k, coords[i])
            u = jax.random.uniform(k, (n_loc,), dtype=jnp.float32)
            return u, len_tot

        sm = _shard_map(begin, mesh=self._tmesh,
                        in_specs=(P(), P(), P(), P()),
                        out_specs=(P(), P()), check_vma=False)
        self._begin_fn = jax.jit(sm)
        return self._begin_fn

    def _get_sub(self):
        if self._sub_fn is not None:
            return self._sub_fn
        cfg, lay, g = self.cfg, self.layout, self.cfg.g
        V, K = self.corpus.n_words, self.cfg.n_topics
        P_rows = self._page_rows

        def sub(u_r, word_rel, doc_r, mask_r, topics, d_block, page,
                colsum, len_tot, dD):
            my = jax.lax.axis_index("model")
            kb0 = my * K
            W_hat, g_vals, g_idx, q_prime = _word_phase(
                page, cfg=cfg, model_axis="model", n_words=V, g=g,
                kb0=kb0, k_local=K, colsum=colsum)
            if lay is None:
                d_rows = lambda d: d_block[d]             # noqa: E731
            else:
                d_rows = lambda d: sparse.densify_rows(   # noqa: E731
                    d_block[d], K)
            new_topics, skip, in_m, k1 = _token_sweep(
                u_r, word_rel, doc_r, d_rows, len_tot, W_hat, g_vals,
                g_idx, q_prime, alpha=cfg.alpha_, g=g, kb0=kb0,
                k_local=K, my=my, model_axis="model", tile=cfg.tile_size)
            wgt = mask_r.astype(jnp.int32)

            def _blk(t):
                rel = t - kb0
                in_blk = (rel >= 0) & (rel < K)
                return jnp.clip(rel, 0, K - 1), jnp.where(in_blk, wgt, 0)

            old_rel, w_old = _blk(topics)
            t_rel, w_new = _blk(new_topics)
            with jax.named_scope("lda.count_update"):
                dD_new = dD.at[doc_r, old_rel].add(-w_old) \
                           .at[doc_r, t_rel].add(w_new)
                dw_page = jnp.zeros((P_rows, K), jnp.int32) \
                    .at[word_rel, old_rel].add(-w_old) \
                    .at[word_rel, t_rel].add(w_new)
            fmask = mask_r.astype(jnp.float32)
            sums = jnp.stack([
                jnp.sum(skip.astype(jnp.float32) * fmask),
                jnp.sum((skip | in_m).astype(jnp.float32) * fmask),
                jnp.sum((new_topics == topics).astype(jnp.float32) * fmask),
                jnp.sum((new_topics == k1).astype(jnp.float32) * fmask),
                jnp.sum(fmask)])                  # every token is drawn
            n_surv = jnp.sum((~skip).astype(jnp.float32) * fmask)
            return new_topics, dD_new, dw_page, n_surv, sums

        sm = _shard_map(sub, mesh=self._tmesh,
                        in_specs=tuple(P() for _ in range(10)),
                        out_specs=tuple(P() for _ in range(5)),
                        check_vma=False)
        self._sub_fn = jax.jit(sm, donate_argnums=(4, 9))
        return self._sub_fn

    def _get_close(self):
        if self._close_fn is not None:
            return self._close_fn
        lay, K = self.layout, self.cfg.n_topics
        if lay is None:
            def close(d_block, dD):
                return d_block + dD
        else:
            def close(d_block, dD):
                d_dense = sparse.densify_rows(d_block, K)
                d_repacked, ov = sparse.pack_rows_sorted(
                    d_dense + dD, lay.d_capacity)
                return d_repacked, ov
        self._close_fn = jax.jit(close, donate_argnums=(0,))
        return self._close_fn

    # -- state construction --------------------------------------------------

    def _pack_d(self, D_s: np.ndarray):
        if self.layout is None:
            return jnp.asarray(D_s)
        return sparse.build_sparse_rows(
            jnp.asarray(D_s), self.layout.d_capacity)

    def _make_state(self, topics_nloc: np.ndarray, D, W, key,
                    clock: int) -> PSStreamState:
        S = self.sc.n_shards
        host = _extend_cols(np.asarray(topics_nloc, np.int32),
                            self._R * self._L, 0)
        server = self._ps_mod.ParameterServer(
            self.owner_layout, self.cfg.n_topics, S,
            staleness=self.dist_cfg.staleness)
        server.load_global(W)
        server.committed = int(clock)
        server.ckpt_clock = int(clock)
        clients = []
        for w in range(S):
            c = self._ps_mod.PSClient(server, w)
            c.clock = int(clock)
            clients.append(c)
        return PSStreamState(
            host_topics=host,
            d_blocks=[self._pack_d(D[w]) for w in range(S)],
            server=server, clients=clients, key=key,
            clocks=np.full(S, int(clock), np.int64),
            cursors=np.zeros(S, np.int64),
            epochs=[None] * S)

    def init_state(self) -> PSStreamState:
        cfg = self.cfg
        key = jax.random.PRNGKey(cfg.seed)
        topics = jax.random.randint(
            jax.random.fold_in(key, 7), self.sc.word_ids.shape, 0,
            cfg.n_topics, dtype=jnp.int32)
        D, W = _host_counts(self.sc, self.corpus, cfg.n_topics,
                            np.asarray(topics))
        return self._make_state(np.asarray(topics), D, W, key, 0)

    # -- the per-worker round ------------------------------------------------

    def _open_round(self, ss: PSStreamState, w: int) -> _PSEpochCarry:
        clock = int(ss.clocks[w])
        u_dev, len_tot = self._get_begin()(
            ss.d_blocks[w], ss.key, jnp.int32(clock), self._coords[w])
        u_host = np.zeros(self._R * self._L, np.float32)
        u_host[:self._n_loc] = np.asarray(u_dev)
        colsum = jnp.asarray(
            ss.clients[w].pull_colsum().astype(np.float32))
        ep = _PSEpochCarry(
            u_host=u_host, len_tot=len_tot, colsum=colsum,
            dD=jnp.zeros((self.sc.m_local, self.cfg.n_topics), jnp.int32),
            start_topics=ss.host_topics[w].copy())
        ss.epochs[w] = ep
        return ep

    def _advance_worker(self, ss: PSStreamState, w: int,
                        max_subs: int | None = None) -> bool:
        """Run worker ``w`` forward by up to ``max_subs`` sub-shards
        (None = to the round close). Returns True iff the round closed."""
        R, L = self._R, self._L
        clock = int(ss.clocks[w])
        client = ss.clients[w]
        ep = ss.epochs[w] or self._open_round(ss, w)
        sub = self._get_sub()
        n_done = 0
        while int(ss.cursors[w]) < R and \
                (max_subs is None or n_done < max_subs):
            r = int(ss.cursors[w])
            if chaos.armed():
                chaos.shard_event(clock, w * R + r)
            cols = slice(r * L, (r + 1) * L)
            base = int(self._bases[w, r])
            page = jnp.asarray(
                client.pull_page(base, base + self._page_rows))
            new_t, ep.dD, dw_page, n_surv, sums = sub(
                jnp.asarray(ep.u_host[cols]),
                jnp.asarray(self._word_rel[w, cols]),
                jnp.asarray(self._st_doc[w, cols]),
                jnp.asarray(self._st_mask[w, cols]),
                jnp.asarray(ss.host_topics[w, cols]),
                ss.d_blocks[w], page, ep.colsum, ep.len_tot, ep.dD)
            client.push_page(base, base + self._page_rows,
                             np.asarray(dw_page))
            ss.host_topics[w, cols] = np.asarray(new_t)
            ep.n_surv += float(n_surv)
            ep.stat_sums += np.asarray(sums, np.float64)
            ss.cursors[w] = r + 1
            n_done += 1
        if int(ss.cursors[w]) < R:
            return False
        # -- round close: fold the D delta, declare the round finished ----
        if self.layout is None:
            ss.d_blocks[w] = self._get_close()(ss.d_blocks[w], ep.dD)
        else:
            ss.d_blocks[w], ov = self._get_close()(ss.d_blocks[w], ep.dD)
            ss.overflow += int(ov)
        acc = ss.stat_rounds.setdefault(
            clock, [0.0, np.zeros(5, np.float64)])
        acc[0] += ep.n_surv
        acc[1] = acc[1] + ep.stat_sums
        ss.epochs[w] = None
        ss.cursors[w] = 0
        ss.clocks[w] = clock + 1
        client.finish_round()        # may commit the round
        self._poll_owner_chaos(ss)
        return True

    def _poll_owner_chaos(self, ss: PSStreamState) -> None:
        """The owner-kill drill: wipe a planned owner at its planned
        committed round, then recover through the snapshot + journal
        replay path — the trajectory must come out bitwise unchanged."""
        if not chaos.armed():
            return
        srv = ss.server
        for o in range(srv.layout.n_owners):
            if chaos.ps_owner_event(o, srv.committed):
                srv.kill_owner(o)
                srv.revive_owner(o, [c.journal for c in ss.clients])

    # -- drivers -------------------------------------------------------------

    def step(self, state):
        raise ValueError(
            "the parameter-server trainer advances by whole rounds "
            "(epochs): use run_fused(state, n_iters)")

    def run_fused(self, ss: PSStreamState, n_iters: int):
        """Advance every worker ``n_iters`` rounds under the SSP clock.

        The scheduler picks, among workers behind the target whose pull
        the staleness gate admits, the one with the lowest
        ``clock + chaos bias``; each pick runs one whole round, so every
        pull within a round observes a single committed version. The
        slowest worker is always admissible (its clock equals the
        committed round), so progress is guaranteed; a chaos
        ``ps_slow_workers`` bias skews the order, forcing the fast
        workers through genuinely stale (but admissible) pulls.
        """
        if chaos.armed():
            chaos.step_range(int(ss.iteration), int(n_iters))
        start = int(ss.iteration)
        target = start + int(n_iters)
        fplan = chaos.plan()
        bias = dict(fplan.ps_slow_workers) if fplan is not None else {}
        S = self.sc.n_shards
        while int(ss.clocks.min()) < target:
            cand = [w for w in range(S)
                    if int(ss.clocks[w]) < target
                    and ss.clients[w].can_advance()]
            w = min(cand, key=lambda i: (int(ss.clocks[i]) + bias.get(i, 0),
                                         i))
            self._advance_worker(ss, w)
        denom = float(max(int(self.sc.mask.sum()), 1))
        rows = []
        for c in range(start, target):
            _n_surv, sums = ss.stat_rounds.pop(c)
            rows.append(sums / denom)
        for c in [c for c in ss.stat_rounds if c < target]:
            del ss.stat_rounds[c]          # rounds reported by run_shards
        m = np.asarray(rows, np.float32).reshape(-1, 5)
        stats = three_branch.ThreeBranchStats(
            frac_skipped=m[:, 0], frac_m_final=m[:, 1],
            frac_unchanged=m[:, 2], frac_at_max=m[:, 3],
            frac_q_branch=np.zeros(len(rows), np.float32),
            frac_phase2_slots=m[:, 4])
        return ss, stats

    def run_shards(self, ss: PSStreamState, n_shards: int = 1):
        """Advance every worker ``n_shards`` sub-shards in lockstep — the
        mid-epoch stepping surface behind ``checkpoint_shards``. Lockstep
        keeps the clocks aligned, which is what makes the mid-epoch
        payload's cut canonical (host_payload refuses skewed clocks)."""
        S = self.sc.n_shards
        for _ in range(max(int(n_shards), 0)):
            for w in range(S):
                self._advance_worker(ss, w, max_subs=1)
        return ss

    # -- checkpointing -------------------------------------------------------

    def host_payload(self, ss: PSStreamState) -> dict:
        from repro.checkpoint.ps_payload import pack_ps_payload
        clocks = ss.clocks
        if int(clocks.max()) != int(clocks.min()):
            raise ValueError(
                "PS payloads cut at an aligned clock, but worker clocks "
                f"are skewed ({clocks.tolist()}): finish the round "
                "(run_fused) or step in lockstep (run_shards) first")
        cut = int(clocks[0])
        t_cut = np.empty_like(ss.host_topics)
        for w in range(self.sc.n_shards):
            ep = ss.epochs[w]
            t_cut[w] = ep.start_topics if ep is not None \
                else ss.host_topics[w]
        out = np.zeros(self.corpus.n_tokens, np.int32)
        for s in range(self.sc.n_shards):
            sel = self.sc.mask[s] > 0
            out[self.sc.global_pos[s][sel]] = \
                t_cut[s][:self._n_loc][sel]
        payload = {"topics_global": out,
                   "key": np.asarray(jax.random.key_data(ss.key)),
                   "iteration": cut}
        if ss.cursors.any():
            payload.update(pack_ps_payload(
                server=ss.server, cursors=ss.cursors,
                done_topics=np.concatenate(
                    [ss.host_topics[w, :int(ss.cursors[w]) * self._L]
                     for w in range(self.sc.n_shards)]
                    or [np.zeros(0, np.int32)]),
                epochs=ss.epochs))
        # a durable checkpoint now covers everything committed: snapshot
        # the owner rows as the revive base and trim the client journals
        ss.server.note_checkpoint(
            ss.server.committed, journals=[c.journal for c in ss.clients])
        return payload

    def state_from_payload(self, payload: dict) -> PSStreamState:
        from repro.checkpoint.ps_payload import unpack_ps_payload
        if int(np.asarray(payload.get("stream_cursor", 0))) > 0:
            raise ValueError(
                "mid-epoch single-host streaming checkpoints restore on "
                "the single-host backend only; the PS trainer resumes "
                "its own ps_* payloads or epoch-boundary payloads")
        tg = np.asarray(payload["topics_global"], np.int32)
        if tg.shape[0] != self.corpus.n_tokens:
            raise ValueError(
                f"checkpoint topics_global has {tg.shape[0]} entries but "
                f"the corpus holds {self.corpus.n_tokens} tokens: the "
                "checkpoint belongs to a different corpus")
        S = self.sc.n_shards
        topics = np.zeros_like(self.sc.word_ids)
        for s in range(S):
            sel = self.sc.mask[s] > 0
            topics[s][sel] = tg[self.sc.global_pos[s][sel]]
        D, W = _host_counts(self.sc, self.corpus, self.cfg.n_topics,
                            topics)
        key = jax.random.wrap_key_data(jnp.asarray(payload["key"]))
        cut = int(payload["iteration"])
        ss = self._make_state(topics, D, W, key, cut)
        ext = unpack_ps_payload(payload)
        if ext is None or not ext.cursors.any():
            return ss
        # -- reopen the cut's partial round ---------------------------------
        # The payload's per-owner rows are the committed state at the cut;
        # they MUST equal the counts derived from the canonical topics
        # (counts are derived state) — a mismatch means a corrupt payload.
        W_stored = ext.gather_w()
        if not np.array_equal(W_stored, W):
            raise ValueError(
                "ps_* payload owner rows disagree with the counts "
                "derived from topics_global: corrupt checkpoint")
        L = self._L
        off = 0
        for w in range(S):
            cur = int(ext.cursors[w])
            if cur == 0:
                continue
            ep = self._open_round(ss, w)   # same key folds → same u bits
            done = ext.done_topics[off:off + cur * L]
            off += cur * L
            ss.host_topics[w, :cur * L] = done
            ss.cursors[w] = cur
            # rebuild the device D delta and the partial-round pushes
            # from the (start, done) topic hist-diff — exact int ops, so
            # the resumed trajectory is bit-identical to the uninterrupted
            # one (pinned in tests/test_ps.py)
            dD_np = np.zeros((self.sc.m_local, self.cfg.n_topics),
                             np.int32)
            client = ss.clients[w]
            drawn = 0
            for r in range(cur):
                cols = slice(r * L, (r + 1) * L)
                m = self._st_mask[w, cols] > 0
                drawn += int(m.sum())
                old = ep.start_topics[cols][m]
                new = done[cols][m]
                doc = self._st_doc[w, cols][m]
                wrel = self._word_rel[w, cols][m]
                np.add.at(dD_np, (doc, old), -1)
                np.add.at(dD_np, (doc, new), 1)
                dw = np.zeros((self._page_rows, self.cfg.n_topics),
                              np.int32)
                np.add.at(dw, (wrel, old), -1)
                np.add.at(dw, (wrel, new), 1)
                base = int(self._bases[w, r])
                client.push_page(base, base + self._page_rows, dw)
            ep.dD = ep.dD + jnp.asarray(dD_np)
            if ext.stat_sums is not None:
                row = ext.stat_sums[w]
                if row.shape[0] == 4:
                    # saved before the phase-2 slot sum was kept: every
                    # real token of the done sub-shards was drawn
                    row = np.append(row, float(drawn))
                ep.stat_sums = row.copy()
                ep.n_surv = float(ext.n_surv[w])
        if off != ext.done_topics.shape[0]:
            raise ValueError(
                "ps_done_topics length disagrees with ps_cursors: "
                "corrupt checkpoint")
        return ss

    # -- introspection -------------------------------------------------------

    def gather_global(self, ss: PSStreamState):
        """Global (D, W) count matrices at the committed cut."""
        if self.layout is None:
            D_sh = np.stack([np.asarray(b) for b in ss.d_blocks])
        else:
            lay = self.layout
            flat = jnp.stack(list(ss.d_blocks)).reshape(
                self.sc.n_shards * self.sc.m_local, -1)
            D_sh = np.asarray(sparse.densify_rows(flat, lay.n_topics)) \
                .reshape(self.sc.n_shards, self.sc.m_local, lay.n_topics)
        K = self.cfg.n_topics
        D = np.zeros((self.corpus.n_docs, K), np.int64)
        for s in range(self.sc.n_shards):
            nd = int(self.sc.docs_per_shard[s])
            D[self.sc.doc_map[s][:nd]] += D_sh[s][:nd]
        return D, ss.server.gather_global()

    def llpt(self, ss: PSStreamState) -> float:
        """LLPT of the committed counts, gathered to the host."""
        D, W = self.gather_global(ss)
        return _host_llpt(self.corpus, self.cfg, D, W)

    def state_nbytes(self, ss: PSStreamState) -> int:
        """Per-host live count bytes: this worker's D block plus the
        LARGEST W owner shard (a host is at most one worker + one owner;
        no host ever holds the full W — the point of the PS design)."""
        d_bytes = max(int(np.asarray(b).nbytes) for b in ss.d_blocks)
        return d_bytes + ss.server.max_owner_nbytes()

    def selfcheck(self, ss: PSStreamState) -> None:
        D, W = self.gather_global(ss)
        invariants.check_dense_counts(
            D, W, n_tokens=self.corpus.n_tokens,
            where=f"ps round boundary (iteration {ss.iteration})")
