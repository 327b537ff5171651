"""Fused LDA training iteration: one donated dispatch, zero host syncs.

Why this module exists (DESIGN notes)
=====================================

EZLDA's central observation is that converged tokens make most per-iteration
work redundant: the three-branch skip (paper §III) removes the sampling work,
and the same convergence heterogeneity removes most of the *update* work —
a token that keeps its topic moves no counts. The seed trainer nevertheless
paid, every iteration:

  * several separate jit dispatches (Ŵ, phase 1, per-chunk phase 2, rebuild),
  * one host sync (``int(n_surv)`` in three_branch.sample) to size the
    Python chunk loop,
  * a full O(N) histogram rebuild of D and W from scratch,
  * an O(V·K) column reduction for Ŵ's denominator.

WarpLDA's lesson is that the *whole iteration*, not just the sampler, must be
restructured around memory behavior; SaberLDA's is that sparsity-aware
updates are where GPU LDA time actually goes. This module applies both:

``fused_step(state) -> state`` is ONE jitted, buffer-donated program that
runs, back to back on device:

  1. Ŵ from the *maintained* column sum (state.colsum, int32 — exact), so
     the O(V·K) reduction disappears;
  2. phase-1 skip for every token (O(g) gathers per token);
  3. survivor compaction + phase 2 over fixed-``capacity`` chunks inside a
     ``lax.fori_loop`` with a static chunk budget of ceil(N/capacity).
     Chunks past the survivor tail are skipped by ``lax.cond`` — correctness
     never depends on the budget, runtime work is ceil(survivors/capacity).
     Phase 2 routes through the Pallas ``sample_fused`` kernel when
     ``config.impl == "pallas"`` (unifying the formerly disjoint
     ``impl="pallas"`` and ``sampler="three_branch"`` paths) and through the
     dense ``exact_three_branch`` reference otherwise;
  4. the incremental delta update: scatter −1/+1 into D/W/colsum only at
     tokens whose topic changed (esca.delta_update_counts), instead of the
     full rebuild. The rebuild (esca.update_counts) stays as the oracle.

``run_fused(state, n_iters)`` wraps the same body in ``lax.scan``, so an
eval-free stretch of iterations is a single dispatch that never touches the
host — no ``int()``, no ``block_until_ready``, no per-iteration Python.

``HybridFusedPipeline`` runs the same architecture over the hybrid sparse
live state (SparseLDAState: packed-ELL D + HybridW, DESIGN.md SS5) —
selected by ``LDAConfig.format == "hybrid"`` — with the phase-2 sampler
dispatched by the T partition and the delta updates landing in the packed
formats.

Tile-scheduled workload balancing (``config.balance == "tiles"``,
paper §V-A, DESIGN.md SS9): each survivor chunk IS a tile of the live
(compacted, word-sorted) survivor stream — equal survivor tokens per
schedulable unit. The tile plan supplies the second level of the paper's
two-level index: a per-chunk word-run window of static size ``win_words``
(initialized from ``core/balance.build_tiles``'s ``max_words_per_tile``
over the static corpus, then RE-PLANNED between scans from the measured
span of the live survivor tiles — three-branch skips shift the word
distribution as convergence heterogeneity kicks in, so the plan tracks
the live stream, not the static corpus). Phase 2 then resolves Ŵ rows
(and per-word stats) from the resident window via the tile-scheduled
kernels (``sample_fused_tiled`` / ``sample_sparse_tiled`` /
``exact_three_branch_tiled``). Chunks whose measured span exceeds the
window cond-fall back to the per-token gather — bit-exactness never
depends on the plan (pinned by tests/test_balance.py).

Capacity planning: the survivor count is data-dependent, so chunk capacity
is chosen from an exponential moving average of survivor counts observed in
*previous* scans (one device→host read per scan, after it completes) and
re-planned only between scans, with power-of-two hysteresis to bound
recompiles. The tile window re-plans on the same cadence from the observed
chunk spans. Inside the compiled region nothing ever depends on a host
value.

PRNG discipline matches LDATrainer.step exactly (split once per iteration,
uniforms drawn in one (N,) batch), so with the same key the fused path
reproduces the reference trainer's topic assignments bit for bit — pinned
by tests/test_fused_step.py.
"""

from __future__ import annotations

import concurrent.futures
import dataclasses
import time
import warnings
from typing import Callable, NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import balance as balance_mod
from repro.core import esca, mh, sparse, three_branch
from repro.kernels import ops as kops
from repro.kernels import sample_fused as _fused
from repro.kernels import sample_warp as _warp
from repro.kernels.runtime import resolve_interpret
from repro.lda import invariants
from repro.runtime import chaos

__all__ = ["FusedState", "FusedPipeline", "HybridFusedPipeline",
           "PrefetchTimeout", "StreamState", "StreamingPipeline",
           "StreamingHybridPipeline",
           "plan_capacity", "plan_window", "plan_tile_capacity",
           "plan_stream_shards", "resolve_residency",
           "STREAM_BYTES_PER_TOKEN", "STREAM_PAYLOAD_KEYS"]

# Per-tile phase-2 working-set budget (capacity · K · 4 B): the CPU-cache /
# VMEM analogue of the paper's shared-memory-sized blocks. Equal-token
# tiles sized to keep their working set resident are what turns the
# structural balance into measured throughput (on XLA:CPU, 16384-token
# chunks ran ~1.7× slower than 1024-token tiles at K=64).
TILE_WORKING_SET_BYTES = 1 << 18


class FusedState(NamedTuple):
    """LDAState + the incrementally maintained Ŵ column sum."""
    topics: jax.Array      # (N,) int32
    D: jax.Array           # (M, K) int32
    W: jax.Array           # (V, K) int32
    colsum: jax.Array      # (K,) int32 == W.sum(axis=0), kept by deltas
    key: jax.Array         # PRNG key
    iteration: jax.Array   # () int32


def scatter_changed_deltas(topics, new_topics, doc_ids, word_ids, mask, *,
                           capacity: int, D, W, colsum):
    """±1 scatters at the CHANGED tokens only, over compacted chunks.

    The shared update engine of both pipelines: semantics of
    esca.delta_update_counts (the oracle the tests pin), but the scatters
    touch ~n_changed elements instead of 2N — at steady state most tokens
    keep their topic, so the update task shrinks with the sampling task.
    ``D``/``W`` may be the live count matrices (dense pipeline) or zero
    delta matrices destined for a packed repack (hybrid pipeline); the
    chunk bodies are cond-guarded so chunks past the changed-token tail
    cost one predicate. Its operations carry the ``lda.count_update``
    scope in the program's op metadata, the phase being no program of its
    own inside the fused, compacted and streamed programs.
    """
    with jax.named_scope("lda.count_update"):
        n = topics.shape[0]
        changed = (new_topics != topics) & (mask > 0)
        rank_c = jnp.cumsum(changed) - 1
        n_chg = (rank_c[-1] + 1).astype(jnp.int32)
        n_chunks = max(1, -(-n // capacity))
        chg_idx = three_branch.compact_survivor_indices(
            rank_c, ~changed, n_chunks * capacity)

        def upd_body(c, carry):
            def run_chunk(carry):
                D, W, colsum = carry
                idx = jax.lax.dynamic_slice(chg_idx, (c * capacity,),
                                            (capacity,))
                w = (idx < n).astype(jnp.int32)   # sentinel slots add 0
                d_c, v_c = doc_ids[idx], word_ids[idx]
                old_c, new_c = topics[idx], new_topics[idx]
                D = D.at[d_c, old_c].add(-w).at[d_c, new_c].add(w)
                W = W.at[v_c, old_c].add(-w).at[v_c, new_c].add(w)
                colsum = colsum.at[old_c].add(-w).at[new_c].add(w)
                return D, W, colsum
            return jax.lax.cond(c * capacity < n_chg, run_chunk,
                                lambda carry: carry, carry)

        return jax.lax.fori_loop(0, n_chunks, upd_body, (D, W, colsum))


def build_warp_proposal(W, colsum, beta: float):
    """Scan-start warp proposal state from the live integer counts.

    Returns ``(w_til, tables, squeue, lqueue, n_small)``: the Ŵ snapshot
    the tables are built from (W̃ — the acceptance ratio keeps gathering
    this as q̃ even after the live counts move on), the Walker alias
    tables over it, and the Vose queue metadata the Pallas kernel needs
    to run the identical pairing loop per tile (core/mh.alias_queues is
    sort-based, so it runs here — once per scan — not in the kernel).
    Built OUTSIDE the donated scan and held fixed across its iterations:
    staleness is sound for MH (DESIGN.md SS12), and one O(V·K) build
    amortizes over every proposal of the scan.
    """
    w_til = esca.compute_w_hat_from_colsum(W, colsum, beta)
    k_total = w_til.shape[1]
    q = w_til / jnp.sum(w_til, axis=1, keepdims=True)
    squeue, lqueue, n_small = mh.alias_queues(q * k_total)
    prob, alias = mh.run_vose(q * k_total, squeue, lqueue, n_small)
    tables = mh.AliasTables(prob=prob, alias=alias, q=q)
    return w_til, tables, squeue, lqueue, n_small


def warp_stats(mask, acc_any, new_topics, old_topics,
               n_cycles: int) -> mh.WarpStats:
    """Per-iteration MH statistics over the REAL (unmasked) tokens."""
    f32 = jnp.float32
    m = (mask > 0).astype(f32)
    denom = jnp.maximum(jnp.sum(m), 1.0)
    return mh.WarpStats(
        frac_accepted=jnp.sum(acc_any.astype(f32) * m) / denom,
        frac_unchanged=jnp.sum(
            (new_topics == old_topics).astype(f32) * m) / denom,
        n_proposals=jnp.float32(2 * n_cycles))


def branch_stats(skip, in_m_acc, new_topics, old_topics, k1, slots):
    """The ThreeBranchStats both pipelines report (Fig 12 fractions);
    ``slots`` is the exact-draw slots phase 2 ran (``chunk_slots``)."""
    f32 = jnp.float32
    n = skip.shape[0]
    return three_branch.ThreeBranchStats(
        frac_skipped=jnp.mean(skip.astype(f32)),
        frac_m_final=jnp.mean((skip | in_m_acc).astype(f32)),
        frac_unchanged=jnp.mean((new_topics == old_topics).astype(f32)),
        frac_at_max=jnp.mean((new_topics == k1).astype(f32)),
        frac_phase2_slots=jnp.minimum(slots.astype(f32) / max(n, 1), 1.0),
    )


def plan_capacity(ema_survivors: float, n_tokens: int, *,
                  target_chunks: int = 8, floor: int = 2048) -> int:
    """Survivor-chunk capacity from the survivor-count EMA.

    Survivor compaction is ONE O(N) scatter per iteration and each chunk is
    an O(capacity) dynamic-slice, so small chunks are cheap: aim for about
    ``target_chunks`` active chunks, which bounds the phase-2 overshoot
    (work beyond the true survivor count) at ~1/target_chunks. Power-of-two
    bucketing gives hysteresis: the jit cache grows logarithmically in
    n_tokens and small EMA wobble never recompiles.
    """
    want = max(float(ema_survivors) / target_chunks, float(floor))
    cap = 1 << max(int(want) - 1, 1).bit_length()
    return int(min(cap, n_tokens))


def plan_tile_capacity(ema_survivors: float, n_tokens: int,
                       n_topics: int, *, floor: int = 128) -> int:
    """Tile size under ``balance="tiles"``: survivor-EMA capacity, capped
    by the working-set budget.

    A phase-2 tile touches ~capacity·K·4 B of gathered rows; keeping that
    inside ``TILE_WORKING_SET_BYTES`` keeps every schedulable unit's
    working set resident (VMEM on TPU, L2 on CPU) — the paper's
    shared-memory-sized block, applied to the live survivor stream.
    """
    budget = TILE_WORKING_SET_BYTES // (4 * max(int(n_topics), 1))
    budget = max(floor, 1 << max(int(budget).bit_length() - 1, 0))
    return max(floor, min(plan_capacity(ema_survivors, n_tokens), budget))


def plan_window(max_span: float, n_words: int, *, floor: int = 64) -> int:
    """Tile word-window size from the observed survivor-chunk word spans.

    The live analogue of ``TilePlan.max_words_per_tile``: the window must
    cover the widest word run any survivor tile currently spans (else that
    chunk cond-falls back to the per-token gather — correct, just
    unamortized). Power-of-two bucketing bounds recompiles exactly like
    ``plan_capacity``; the window never exceeds the vocabulary (at V the
    tiled path degenerates to the plain one and is skipped statically).
    """
    want = max(float(max_span), float(floor))
    win = 1 << max(int(want) - 1, 1).bit_length()
    return int(min(win, n_words))


class FusedPipeline:
    """Owns the compiled fused step/scan for one (corpus, config) pair.

    Built from the same padded device arrays as LDATrainer; see the module
    docstring for the architecture (including the ``balance="tiles"``
    tile-scheduled phase-2 dispatch).
    """

    def __init__(self, word_ids: jax.Array, doc_ids: jax.Array,
                 mask: jax.Array, *, n_docs: int, n_words: int, config,
                 n_tokens: int | None = None):
        self.config = config
        self.word_ids = word_ids
        self.doc_ids = doc_ids
        self.mask = mask
        self.n_docs = n_docs
        self.n_words = n_words
        # disk-native streaming passes the padded length explicitly and
        # NO host token arrays (the file layer is the source of truth)
        self.n_tokens = int(n_tokens if n_tokens is not None
                            else word_ids.shape[0])
        cap = getattr(config, "survivor_capacity", None)
        self.capacity = int(cap) if cap else self.n_tokens
        self.capacity = min(max(self.capacity, 1), self.n_tokens)
        # An explicitly configured capacity is pinned: the EMA replanner
        # keeps tracking survivors but never overrides the user's knob.
        self._capacity_pinned = cap is not None
        self._surv_ema: float | None = None
        self._step_cache: dict[tuple, Callable] = {}
        self._interpret = resolve_interpret(None)
        # -- warp MH engine (sampler="warp", DESIGN.md SS12) ---------------
        self.sampler = getattr(config, "sampler", "three_branch")
        self._proposal_fn: Callable | None = None
        if self.sampler == "warp":
            # static doc→token index for the positional doc proposal;
            # host-built once (the corpus layout never moves)
            self.doc_index = mh.build_doc_index(doc_ids, mask, n_docs)
        # -- tile-scheduled balancing (paper §V-A, DESIGN.md SS9) ----------
        self.balance = getattr(config, "balance", "none")
        self._span_ema: float | None = None
        self.win_words = n_words
        self.tile_plan = None
        if self.balance == "tiles":
            if not self._capacity_pinned:
                # full-survivorship tile size, working-set capped from the
                # start (the survivor EMA refines it between scans)
                self.capacity = plan_tile_capacity(
                    self.n_tokens, self.n_tokens, config.n_topics)
            self._plan_tiles(word_ids)

    def _plan_tiles(self, word_ids) -> None:
        """Initial plan over the STATIC corpus stream at the current tile
        size; re-planned live from observed survivor spans. The streaming
        subclass overrides this with per-shard plans (one pass over the
        stream, not two)."""
        self.tile_plan = balance_mod.build_tiles_from_word_ids(
            np.asarray(word_ids), min(self.capacity, self.n_tokens))
        self.win_words = plan_window(self.tile_plan.max_words_per_tile,
                                     self.n_words)

    # -- state conversion --------------------------------------------------

    def from_lda_state(self, state) -> FusedState:
        """Attach the derived colsum to a trainer LDAState.

        Copies the count/topic buffers: step/run_fused DONATE their input,
        and aliasing the caller's LDAState into a donated pytree would
        silently invalidate it. One copy per entry into the fused pipeline,
        never per iteration.
        """
        colsum = jnp.sum(state.W, axis=0, dtype=jnp.int32)
        key = jax.random.wrap_key_data(jnp.copy(
            jax.random.key_data(state.key)))
        return FusedState(topics=jnp.copy(state.topics),
                          D=jnp.copy(state.D), W=jnp.copy(state.W),
                          colsum=colsum, key=key,
                          iteration=jnp.copy(state.iteration))

    def to_lda_state(self, fstate: FusedState):
        from repro.lda.model import LDAState
        return LDAState(topics=fstate.topics, D=fstate.D, W=fstate.W,
                        key=fstate.key, iteration=fstate.iteration)

    def _n_real_tokens(self) -> int:
        n = getattr(self, "_n_real", None)
        if n is None:
            n = int(np.asarray(self.mask).astype(np.int64).sum())
            self._n_real = n
        return n

    def selfcheck(self, fstate) -> None:
        """Count-invariant tripwire on the live state (``config.selfcheck``):
        host-side, so callers run it at chunk boundaries, not per step."""
        invariants.check_dense_counts(
            fstate.D, fstate.W, fstate.colsum,
            n_tokens=self._n_real_tokens(),
            where=f"chunk boundary (iteration {int(fstate.iteration)})")

    # -- warp proposal state (built once per scan, outside the donation) ---

    def _build_proposal(self, fstate) -> tuple:
        """Alias tables + queues over the SCAN-START W̃ (see
        build_warp_proposal). Under ``config.selfcheck`` the freshly built
        tables run the alias invariants before the scan consumes them."""
        if self._proposal_fn is None:
            beta = self.config.beta
            self._proposal_fn = jax.jit(
                lambda W, colsum: build_warp_proposal(W, colsum, beta))
        prop = self._proposal_fn(*self._proposal_counts(fstate))
        if getattr(self.config, "selfcheck", False):
            tables = prop[1]
            invariants.check_alias_tables(
                tables.prob, tables.alias, tables.q,
                where=f"warp proposal build (iteration "
                      f"{int(fstate.iteration)})")
        return prop

    def _proposal_counts(self, fstate) -> tuple:
        """(W, colsum) the proposal builds from; the hybrid pipeline
        overrides this with its packed-state densification."""
        return fstate.W, fstate.colsum

    # -- tile helpers (traced) ---------------------------------------------

    # a word window must be MUCH narrower than the vocabulary to beat the
    # plain per-token gather (the slice costs one window copy per chunk);
    # wider streams still run tile-scheduled, just without the window
    WINDOW_VOCAB_FRACTION = 4

    def _use_tiles(self, win_words: int) -> bool:
        return self.balance == "tiles" \
            and win_words * self.WINDOW_VOCAB_FRACTION <= self.n_words

    def _chunk_run(self, v_c, idx, n_stream: int | None = None):
        """(first_word, last_word) over a chunk's valid tokens — the live
        per-tile word-run metadata (TilePlan's two-level index, computed
        on the fly for the survivor stream). An all-sentinel chunk yields
        (n_words-1, 0), whose negative span always passes the fits test.
        ``n_stream`` is the length of the token stream the indices refer
        to: the full resident stream by default, one epoch shard when the
        streaming pipeline drives this per shard."""
        valid = idx < (self.n_tokens if n_stream is None else n_stream)
        vmin = jnp.min(jnp.where(valid, v_c, self.n_words - 1))
        vmax = jnp.max(jnp.where(valid, v_c, 0))
        return vmin.astype(jnp.int32), vmax.astype(jnp.int32)

    def _max_chunk_span(self, surv_idx, n_chunks: int, capacity: int, *,
                        word_ids, n_stream: int | None = None):
        """Max word span over the scan's survivor tiles (for re-planning).

        One (n_chunks·capacity) gather per iteration — O(N) like the
        compaction itself; read back on the host only between scans.
        ``n_stream`` defaults to the resident stream; the streaming
        pipeline passes its shard-local (word_ids, n_stream).
        """
        n = self.n_tokens if n_stream is None else n_stream
        idx_m = surv_idx.reshape(n_chunks, capacity)
        valid = idx_m < n
        v = word_ids[jnp.minimum(idx_m, n - 1)]
        vmin = jnp.min(jnp.where(valid, v, self.n_words - 1), axis=1)
        vmax = jnp.max(jnp.where(valid, v, 0), axis=1)
        span = jnp.where(jnp.any(valid, axis=1), vmax - vmin + 1, 0)
        return jnp.max(span).astype(jnp.int32)

    def _dense_chunk_sampler(self, u, word_ids, doc_ids, D, W_hat,
                             k1_per_word, *, win_words: int,
                             n_stream: int | None = None):
        """Build the phase-2 ``sample_chunk(idx)`` closure (both pipelines).

        With tiles on, each chunk resolves its live word run and samples
        through the tile-scheduled kernel against a ``(win_words, K)``
        resident Ŵ window; a chunk whose span outgrows the window (the
        live distribution drifted since the last re-plan) cond-falls back
        to the per-token gather. Identical row values either way ⇒ the
        tiled dispatch is bit-equal to the untiled one.
        """
        cfg = self.config
        alpha = cfg.alpha_
        use_tiles = self._use_tiles(win_words)

        if cfg.impl == "pallas":

            def draw(idx, first):
                u_c, v_c, d_c = u[idx], word_ids[idx], doc_ids[idx]
                d_rows = D[d_c]
                if first is None:
                    t_c, m, s, q = _fused.sample_fused(
                        u_c, d_rows, W_hat[v_c], alpha=alpha,
                        interpret=self._interpret)
                else:
                    t_c, m, s, q = _fused.sample_fused_tiled(
                        u_c, d_rows, W_hat, v_c, first, alpha=alpha,
                        win_words=win_words, interpret=self._interpret)
                return t_c, u_c * (m + s + q) < m

            return self._tile_dispatch(draw, word_ids, win_words=win_words,
                                       n_stream=n_stream)

        def sample_chunk(idx):
            u_c, v_c, d_c = u[idx], word_ids[idx], doc_ids[idx]
            if not use_tiles:
                return three_branch.exact_three_branch(
                    u_c, v_c, d_c, k1_per_word, D, W_hat,
                    alpha=alpha, tile_size=cfg.tile_size)
            first, last = self._chunk_run(v_c, idx, n_stream)
            first = jnp.clip(first, 0, self.n_words - win_words)

            def tiled(_):
                w_win = jax.lax.dynamic_slice(
                    W_hat, (first, 0), (win_words, W_hat.shape[1]))
                k1_win = jax.lax.dynamic_slice(k1_per_word, (first,),
                                               (win_words,))
                local = jnp.clip(v_c - first, 0, win_words - 1)
                return three_branch.exact_three_branch_tiled(
                    u_c, local, d_c, k1_win, D, w_win, alpha=alpha,
                    tile_size=cfg.tile_size)

            def untiled(_):
                return three_branch.exact_three_branch(
                    u_c, v_c, d_c, k1_per_word, D, W_hat,
                    alpha=alpha, tile_size=cfg.tile_size)

            return jax.lax.cond(last - first < win_words, tiled, untiled,
                                None)

        return sample_chunk

    def _tile_dispatch(self, draw, word_ids, *, win_words: int,
                       n_stream: int | None):
        """``sample_chunk(idx)`` running a Pallas ``draw(idx, first)`` one
        token tile at a time (``three_branch.map_token_tiles``). With tiles
        on, a chunk whose live word run fits the window draws against it
        (``first`` is the run's first word); any other chunk passes
        ``first=None``, the per-token row gather."""
        tile = self.config.tile_size

        def untiled(idx):
            return three_branch.map_token_tiles(lambda t: draw(t, None),
                                                idx, tile)

        if not self._use_tiles(win_words):
            return untiled

        def sample_chunk(idx):
            first, last = self._chunk_run(word_ids[idx], idx, n_stream)
            return jax.lax.cond(
                last - first < win_words,
                lambda i: three_branch.map_token_tiles(
                    lambda t: draw(t, first), i, tile),
                untiled, idx)

        return sample_chunk

    def _sparse_tail_sampler(self, u, word_ids, doc_ids, d_packed, d_dense,
                             W_hat, stats_w, *, win_words: int,
                             n_stream: int | None = None):
        """Phase-2 ``sample_chunk(idx)`` for tail-word survivors under
        ``tail_sampler="sparse"`` (the hybrid pipelines): the O(L) Pallas
        draw over packed D rows plus the Q' fallback
        (kernels/ops.sparse_tail_draw, or the window-resolved
        ``sparse_tail_draw_tiled`` under ``balance="tiles"``)."""
        alpha = self.config.alpha_
        k1_per_word = stats_w.k[:, 0]

        def draw(idx, first):
            u_c, v_c, d_c = u[idx], word_ids[idx], doc_ids[idx]
            k1 = k1_per_word[v_c]
            b1 = d_dense[d_c, k1].astype(jnp.float32)
            if first is None:
                t_c, _nq, in_m = kops.sparse_tail_draw(
                    u_c, d_packed[d_c], W_hat[v_c], k1, stats_w.a[v_c, 0],
                    b1, stats_w.q_prime[v_c], alpha=alpha,
                    interpret=self._interpret)
            else:
                t_c, _nq, in_m = kops.sparse_tail_draw_tiled(
                    u_c, d_packed[d_c], W_hat, v_c, first, k1_per_word,
                    stats_w.a[:, 0], stats_w.q_prime, b1, alpha=alpha,
                    win_words=win_words, interpret=self._interpret)
            return t_c, in_m

        return self._tile_dispatch(draw, word_ids, win_words=win_words,
                                   n_stream=n_stream)

    def _warp_chunk_sampler(self, topics, t_doc, t_word, u_draw, u_acc,
                            word_ids, doc_ids, D, W_hat, prop, *,
                            win_words: int, n_stream: int | None = None):
        """Phase-2 ``sample_chunk(idx)`` closure for the warp MH engine.

        The XLA path runs the accept/reject cycle with direct scalar
        gathers — O(1) per token, no (capacity, K) row materialization
        anywhere, which is where the ≥2x over the exact sampler comes
        from. The Pallas path ships the chunk's word-run window (live Ŵ,
        stale W̃, Vose queues) into the tile kernel, which rebuilds the
        window's alias tables in VMEM and replays the SAME uniforms —
        bit-equal to the XLA chain by table row-independence (pinned by
        tests/test_warp_sampler.py). A chunk whose span outgrows the
        window cond-falls back to the full-vocabulary window.
        """
        cfg = self.config
        alpha, n_cycles = cfg.alpha_, cfg.mh_cycles
        w_til, tables, squeue, lqueue, n_small = prop
        use_tiles = self._use_tiles(win_words)

        def xla_chain(idx):
            v_c, d_c = word_ids[idx], doc_ids[idx]
            s, n_acc = mh.mh_chain(
                topics[idx], t_doc[:, idx], t_word[:, idx],
                u_acc[:, :, idx],
                lookup_d=lambda k: D[d_c, k].astype(jnp.float32),
                lookup_w=lambda k: W_hat[v_c, k],
                lookup_q=lambda k: tables.q[v_c, k],
                alpha=alpha)
            return s, n_acc > 0

        if cfg.impl != "pallas":
            return xla_chain

        def sample_chunk(idx):
            v_c, d_c = word_ids[idx], doc_ids[idx]
            args = (topics[idx], D[d_c], t_doc[:, idx], u_draw[:, :, idx],
                    u_acc[:, :, idx], W_hat, w_til, squeue, lqueue,
                    n_small, v_c)

            def full(_):
                return _warp.sample_warp_tiled(
                    *args, jnp.int32(0), alpha=alpha, n_cycles=n_cycles,
                    win_words=self.n_words, interpret=self._interpret)

            if not use_tiles:
                s, n_acc = full(None)
                return s, n_acc > 0
            first, last = self._chunk_run(v_c, idx, n_stream)

            def tiled(_):
                return _warp.sample_warp_tiled(
                    *args, first, alpha=alpha, n_cycles=n_cycles,
                    win_words=win_words, interpret=self._interpret)

            s, n_acc = jax.lax.cond(last - first < win_words, tiled,
                                    full, None)
            return s, n_acc > 0

        return sample_chunk

    def _warp_iteration(self, fstate: FusedState, prop, toks, *,
                        capacity: int, win_words: int):
        """One warp MH iteration: proposals → chain → delta update.

        Slots into the identical survivor-compaction machinery as the
        exact iteration — here "skip" is just the padding mask (MH has no
        phase-1 convergence skip; every real token runs its chain), so
        the chunking/tiling stay pure performance knobs and the delta
        scatter still shrinks with the unchanged fraction. PRNG
        discipline mirrors LDATrainer.step + mh.sample_warp (split once,
        then 3-way), so a 1-iteration scan is bit-equal to the stepwise
        reference path.
        """
        cfg = self.config
        n, n_cycles = self.n_tokens, cfg.mh_cycles
        word_ids, doc_ids, mask, doc_index = toks
        topics, D, W, colsum, key, iteration = fstate
        w_til, tables, _squeue, _lqueue, _n_small = prop

        key, sub = jax.random.split(key)
        kd, kw, ka = jax.random.split(sub, 3)
        W_hat = esca.compute_w_hat_from_colsum(W, colsum, cfg.beta)
        t_doc = mh.doc_proposals(kd, topics, doc_ids, doc_index,
                                 n_topics=cfg.n_topics, alpha=cfg.alpha_,
                                 n_cycles=n_cycles)
        t_word, u_draw = mh.word_proposals(kw, word_ids, tables,
                                           n_cycles=n_cycles)
        u_acc = jax.random.uniform(ka, (n_cycles, 2, n),
                                   dtype=jnp.float32)

        skip = mask == 0
        rank, n_surv = three_branch.survivor_rank(skip)
        n_chunks = max(1, -(-n // capacity))
        surv_idx = three_branch.compact_survivor_indices(
            rank, skip, n_chunks * capacity)
        max_span = self._max_chunk_span(
            surv_idx, n_chunks, capacity, word_ids=word_ids) \
            if self.balance == "tiles" else jnp.int32(0)

        sample_chunk = self._warp_chunk_sampler(
            topics, t_doc, t_word, u_draw, u_acc, word_ids, doc_ids, D,
            W_hat, prop, win_words=win_words)
        new_topics, acc_any = three_branch.run_survivor_chunks(
            surv_idx, n_surv, topics,
            capacity=capacity, n_chunks=n_chunks, sample_chunk=sample_chunk)

        D, W, colsum = scatter_changed_deltas(
            topics, new_topics, doc_ids, word_ids, mask,
            capacity=capacity, D=D, W=W, colsum=colsum)
        st = warp_stats(mask, acc_any, new_topics, topics, n_cycles)
        new_state = FusedState(topics=new_topics, D=D, W=W, colsum=colsum,
                               key=key, iteration=iteration + 1)
        return new_state, st, n_surv, max_span

    # -- the fused iteration body (traced; no host interaction) ------------

    def _iteration(self, fstate: FusedState, toks, *, capacity: int,
                   win_words: int):
        cfg = self.config
        alpha, g = cfg.alpha_, cfg.g
        word_ids, doc_ids, mask = toks
        n = self.n_tokens
        topics, D, W, colsum, key, iteration = fstate

        key, sub = jax.random.split(key)
        W_hat = esca.compute_w_hat_from_colsum(W, colsum, cfg.beta)
        stats_w = three_branch.word_stats(W_hat, g=g, alpha=alpha)
        u = jax.random.uniform(sub, (n,), dtype=jnp.float32)
        dec = three_branch.skip_phase(u, word_ids, doc_ids, D, stats_w,
                                      g=g, alpha=alpha)
        rank, n_surv = three_branch.survivor_rank(dec.skip)
        k1_per_word = stats_w.k[:, 0]
        n_chunks = max(1, -(-n // capacity))
        surv_idx = three_branch.compact_survivor_indices(
            rank, dec.skip, n_chunks * capacity)
        max_span = self._max_chunk_span(
            surv_idx, n_chunks, capacity, word_ids=word_ids) \
            if self.balance == "tiles" else jnp.int32(0)

        sample_chunk = self._dense_chunk_sampler(
            u, word_ids, doc_ids, D, W_hat, k1_per_word,
            win_words=win_words)
        new_topics, in_m_acc = three_branch.run_survivor_chunks(
            surv_idx, n_surv, dec.k1,
            capacity=capacity, n_chunks=n_chunks, sample_chunk=sample_chunk)

        # The incremental delta update (see scatter_changed_deltas) lands
        # directly in the live dense matrices here.
        D, W, colsum = scatter_changed_deltas(
            topics, new_topics, doc_ids, word_ids, mask,
            capacity=capacity, D=D, W=W, colsum=colsum)
        st = branch_stats(dec.skip, in_m_acc, new_topics, topics, dec.k1,
                          three_branch.chunk_slots(n_surv, capacity, n))
        new_state = FusedState(topics=new_topics, D=D, W=W, colsum=colsum,
                               key=key, iteration=iteration + 1)
        return new_state, st, n_surv, max_span

    # -- compiled entry points --------------------------------------------

    def _token_args(self) -> tuple:
        """The corpus arrays the compiled scan reads, handed to it as
        arguments: jit embeds a closed-over array in the program as a
        constant, which is a copy in every executable and gigabytes of
        program text at corpus scale."""
        toks = (self.word_ids, self.doc_ids, self.mask)
        return toks + (self.doc_index,) if self.sampler == "warp" else toks

    def _get_fn(self, n_iters: int) -> Callable:
        """(state, toks[, prop]) -> (state, stats, n_surv, max_span) for
        a scan; ``toks`` is ``_token_args()``.

        With ``sampler="warp"`` the compiled scan takes the scan-start
        proposal state as a third (undonated) argument — the tables stay
        fixed across the scan's iterations (the staleness argument,
        DESIGN.md SS12) while the counts keep moving under donation.
        """
        sig = (n_iters, self.capacity, self.win_words)
        fn = self._step_cache.get(sig)
        if fn is None:
            capacity, win = self.capacity, self.win_words
            if self.sampler == "warp":

                def multi(fstate, toks, prop):
                    def body(carry, _):
                        st, stats, n_surv, span = self._warp_iteration(
                            carry, prop, toks, capacity=capacity,
                            win_words=win)
                        return st, (stats, n_surv, span)
                    fstate, (stats, n_surv, span) = jax.lax.scan(
                        body, fstate, None, length=n_iters)
                    return fstate, stats, n_surv, span
            else:

                def multi(fstate, toks):
                    def body(carry, _):
                        st, stats, n_surv, span = self._iteration(
                            carry, toks, capacity=capacity, win_words=win)
                        return st, (stats, n_surv, span)
                    fstate, (stats, n_surv, span) = jax.lax.scan(
                        body, fstate, None, length=n_iters)
                    return fstate, stats, n_surv, span

            fn = jax.jit(multi, donate_argnums=(0,))
            self._step_cache[sig] = fn
        return fn

    def _dispatch(self, fn: Callable, fstate):
        if self.sampler == "warp":
            return fn(fstate, self._token_args(),
                      self._build_proposal(fstate))
        return fn(fstate, self._token_args())

    def step(self, fstate: FusedState):
        """One fused iteration — a single donated dispatch."""
        fstate, stats, n_surv, _ = self._dispatch(self._get_fn(1), fstate)
        squeeze = lambda t: jax.tree.map(lambda x: x[0], t)
        return fstate, squeeze(stats), squeeze(n_surv)

    def run_fused(self, fstate: FusedState, n_iters: int,
                  replan: bool = True):
        """n_iters iterations in one dispatch (lax.scan; no host syncs).

        Returns (state, stats, n_surv) with a leading (n_iters,) axis on
        the stats/survivor leaves. With ``replan=True`` the survivor counts
        (and, under ``balance="tiles"``, the survivor-tile word spans) are
        read back once per scan (after it completes) to update the EMAs
        and possibly re-bucket the chunk capacity / re-tile the window for
        the NEXT scan.
        """
        fstate, stats, n_surv, span = self._dispatch(
            self._get_fn(int(n_iters)), fstate)
        if replan:
            self.note_survivors(n_surv)
            if self.balance == "tiles":
                self.note_spans(span)
        return fstate, stats, n_surv

    # -- between-scan capacity planning (host side) ------------------------

    def note_survivors(self, n_surv, decay: float = 0.7) -> None:
        vals = np.atleast_1d(np.asarray(n_surv)).astype(np.float64)
        ema = self._surv_ema
        for v in vals:
            ema = float(v) if ema is None else decay * ema + (1 - decay) * v
        self._surv_ema = ema
        if not self._capacity_pinned:
            self.capacity = plan_tile_capacity(
                ema, self.n_tokens, self.config.n_topics) \
                if self.balance == "tiles" \
                else plan_capacity(ema, self.n_tokens)

    def note_spans(self, spans, decay: float = 0.7) -> None:
        """Re-tile: update the live word-span EMA and re-plan the window.

        The EMA is floored at the newest observed max so the window only
        lags on SHRINK, never on growth — an undershot window silently
        costs the per-token fallback gather, an overshot one only VMEM.
        """
        m = float(np.max(np.atleast_1d(np.asarray(spans))))
        ema = self._span_ema
        self._span_ema = m if ema is None \
            else max(m, decay * ema + (1 - decay) * m)
        self.win_words = plan_window(self._span_ema, self.n_words)


class HybridFusedPipeline(FusedPipeline):
    """The fused iteration over the hybrid sparse live state (DESIGN.md SS5).

    Same architecture as FusedPipeline (single donated dispatch, survivor
    chunking, lax.scan stretches, EMA capacity planning, tile-scheduled
    dispatch under ``balance="tiles"`` — all inherited), but the training
    state is a SparseLDAState: packed-ELL D rows and HybridW (dense head +
    bucketed packed tail), with the ±1 delta updates landing directly in
    the packed formats.

    Cost shape (why the body looks the way it does): XLA:CPU scatters and
    sorts price per ENTRY (~10M/s) while gathers and elementwise run two
    orders of magnitude faster, so anything O(tokens × slots) — or even a
    per-slot scatter — is ruinous. The packed rows therefore keep their
    slots SORTED BY COLUMN (pack_rows_sorted), which makes both directions
    scatter-free: each iteration (a) densifies the packed state ONCE at
    matrix shape via batched binary search (densify_rows_sorted), runs the
    identical dense-speed sampling phases (bit-exact by construction:
    densified integers are exact, Ŵ comes from the same
    compute_w_hat_from_colsum), then (b) accumulates the iteration's ±1
    moves into transient dense delta matrices (the same compacted
    changed-token scatters the dense pipeline uses — the update task still
    shrinks with convergence) and repacks matrix + delta back to sorted
    slots. This mirrors the paper's own kernels, which densify D/Ŵ rows
    into shared memory per block while the formats at rest stay packed.
    The per-token incremental ell_* ops remain the update path where
    per-token semantics are required (the distributed trainer) and the
    semantics oracle for these repacks.

    The three-branch sampler dispatches by the T partition (word-sorted
    token list, split at layout.v_dense — a STATIC boundary). With the
    default ``tail_sampler="exact"`` both partitions route through the
    same densified exact sweep (Pallas ``sample_fused`` when config.impl
    == "pallas"), so the two routes coincide and run as one compaction —
    bit-exact vs the dense reference trainer end to end.
    ``tail_sampler="sparse"`` splits the dispatch: tail-word survivors go
    through the O(L) Pallas ``sample_sparse`` kernel + Q' fallback over
    the packed D rows (kernels/ops.sparse_tail_draw — the tile-scheduled
    ``sparse_tail_draw_tiled`` under ``balance="tiles"``) — the paper's
    S'/Q' decomposition, which draws from the identical distribution but
    sums branch masses in a different order, so it is
    convergence-equivalent rather than bit-equal (the documented trade in
    DESIGN.md SS5).
    """

    def __init__(self, word_ids: jax.Array, doc_ids: jax.Array,
                 mask: jax.Array, *, n_docs: int, n_words: int, config,
                 corpus):
        super().__init__(word_ids, doc_ids, mask, n_docs=n_docs,
                         n_words=n_words, config=config)
        from repro.lda.model import HybridLayout
        self.layout = HybridLayout.build(corpus, config)
        head = np.asarray(word_ids) < self.layout.v_dense
        self.n_head = int(head.sum())
        self.n_tail = int((~head).sum())

    # -- state conversion --------------------------------------------------

    def from_lda_state(self, state):
        """Dense LDAState -> SparseLDAState (fresh buffers: donation-safe)."""
        return self.layout.to_sparse(state)

    def to_lda_state(self, fstate):
        return self.layout.to_dense(fstate)

    def selfcheck(self, fstate) -> None:
        invariants.check_packed_counts(
            fstate.colsum, fstate.overflow,
            n_tokens=self._n_real_tokens(),
            where=f"chunk boundary (iteration {int(fstate.iteration)})")

    def _proposal_counts(self, hs) -> tuple:
        # warp tables build over the DENSIFIED W (exact integers) — one
        # eager densify per scan, not per iteration
        w_parts = [hs.W_head] + [
            sparse.densify_rows_sorted(b, self.layout.n_topics)
            for b in hs.W_tail]
        w_int = jnp.concatenate(w_parts, axis=0) if len(w_parts) > 1 \
            else hs.W_head
        return w_int, hs.colsum

    def _repack_counts(self, d_new, w_new, overflow):
        """Updated dense matrices -> sorted repack (scatter-free; the
        overflow tripwire stays 0 because capacities are row-nnz upper
        bounds). Shared by the exact and warp iteration bodies."""
        lay = self.layout
        d_packed, ov_d = sparse.pack_rows_sorted(d_new, lay.d_capacity)
        overflow = overflow + ov_d
        w_head = w_new[:lay.v_dense]             # HybridW dense-head part
        new_tail = []
        for b in range(len(lay.tail_caps)):
            start = lay.tail_starts[b]
            end = lay.tail_starts[b + 1] if b + 1 < len(lay.tail_starts) \
                else lay.n_words
            bucket, ov_b = sparse.pack_rows_sorted(w_new[start:end],
                                                   lay.tail_caps[b])
            new_tail.append(bucket)
            overflow = overflow + ov_b
        return d_packed, w_head, tuple(new_tail), overflow

    def _warp_iteration(self, hs, prop, toks, *, capacity: int,
                        win_words: int):
        """The warp MH iteration over the hybrid packed state: densify
        once (exact integers), run the dense warp machinery bit-for-bit,
        repack once. The T partition never splits — the MH chain reads
        rows of the densified matrices directly, so head and tail words
        route identically (``tail_sampler`` is an exact-sampler knob)."""
        cfg, lay = self.config, self.layout
        n, n_cycles = self.n_tokens, cfg.mh_cycles
        word_ids, doc_ids, mask, doc_index = toks
        k_total = lay.n_topics
        topics, d_packed, w_head, w_tail, colsum, overflow, key, iteration \
            = hs
        _w_til, tables, _squeue, _lqueue, _n_small = prop

        key, sub = jax.random.split(key)
        kd, kw, ka = jax.random.split(sub, 3)
        d_dense = sparse.densify_rows_sorted(d_packed, k_total)
        w_parts = [w_head] + [sparse.densify_rows_sorted(b, k_total)
                              for b in w_tail]
        w_int = jnp.concatenate(w_parts, axis=0) if len(w_parts) > 1 \
            else w_head
        w_hat = esca.compute_w_hat_from_colsum(w_int, colsum, cfg.beta)
        t_doc = mh.doc_proposals(kd, topics, doc_ids, doc_index,
                                 n_topics=cfg.n_topics, alpha=cfg.alpha_,
                                 n_cycles=n_cycles)
        t_word, u_draw = mh.word_proposals(kw, word_ids, tables,
                                           n_cycles=n_cycles)
        u_acc = jax.random.uniform(ka, (n_cycles, 2, n),
                                   dtype=jnp.float32)

        skip = mask == 0
        rank, n_surv = three_branch.survivor_rank(skip)
        n_chunks = max(1, -(-n // capacity))
        surv_idx = three_branch.compact_survivor_indices(
            rank, skip, n_chunks * capacity)
        max_span = self._max_chunk_span(
            surv_idx, n_chunks, capacity, word_ids=word_ids) \
            if self.balance == "tiles" else jnp.int32(0)

        sample_chunk = self._warp_chunk_sampler(
            topics, t_doc, t_word, u_draw, u_acc, word_ids, doc_ids,
            d_dense, w_hat, prop, win_words=win_words)
        new_topics, acc_any = three_branch.run_survivor_chunks(
            surv_idx, n_surv, topics,
            capacity=capacity, n_chunks=n_chunks, sample_chunk=sample_chunk)

        d_new, w_new, colsum = scatter_changed_deltas(
            topics, new_topics, doc_ids, word_ids, mask, capacity=capacity,
            D=d_dense, W=w_int, colsum=colsum)
        d_packed, w_head, w_tail, overflow = self._repack_counts(
            d_new, w_new, overflow)
        st = warp_stats(mask, acc_any, new_topics, topics, n_cycles)
        from repro.lda.model import SparseLDAState
        new_state = SparseLDAState(
            topics=new_topics, D=d_packed, W_head=w_head, W_tail=w_tail,
            colsum=colsum, overflow=overflow, key=key,
            iteration=iteration + 1)
        return new_state, st, n_surv, max_span

    # -- the fused iteration body (traced; no host interaction) ------------

    def _iteration(self, hs, toks, *, capacity: int, win_words: int):
        cfg, lay = self.config, self.layout
        alpha, g = cfg.alpha_, cfg.g
        word_ids, doc_ids, mask = toks
        n = self.n_tokens
        k_total = lay.n_topics
        v_dense = lay.v_dense
        topics, d_packed, w_head, w_tail, colsum, overflow, key, iteration \
            = hs

        key, sub = jax.random.split(key)
        # Matrix-shaped, scatter-free densification (see class doc); the
        # densified integers are exact, so everything downstream is the
        # dense pipeline's arithmetic, bit for bit.
        d_dense = sparse.densify_rows_sorted(d_packed, k_total)
        w_parts = [w_head] + [sparse.densify_rows_sorted(b, k_total)
                              for b in w_tail]
        w_int = jnp.concatenate(w_parts, axis=0) if len(w_parts) > 1 \
            else w_head
        w_hat = esca.compute_w_hat_from_colsum(w_int, colsum, cfg.beta)
        stats_w = three_branch.word_stats(w_hat, g=g, alpha=alpha)
        u = jax.random.uniform(sub, (n,), dtype=jnp.float32)
        dec = three_branch.skip_phase(u, word_ids, doc_ids, d_dense,
                                      stats_w, g=g, alpha=alpha)
        k1_per_word = stats_w.k[:, 0]

        dense_chunk = self._dense_chunk_sampler(
            u, word_ids, doc_ids, d_dense, w_hat, k1_per_word,
            win_words=win_words)
        sparse_tail_chunk = self._sparse_tail_sampler(
            u, word_ids, doc_ids, d_packed, d_dense, w_hat, stats_w,
            win_words=win_words)

        # -- phase 2, dispatched by the T partition (static split). With
        # the exact tail sampler both partitions route identically, so they
        # run as ONE compaction (bit-equal to the dense pipeline's order).
        if cfg.tail_sampler == "sparse" and self.n_tail:
            head_mask = word_ids < v_dense
            segments = [(head_mask, self.n_head, dense_chunk),
                        (~head_mask, self.n_tail, sparse_tail_chunk)]
        else:
            segments = [(None, n, dense_chunk)]
        new_topics = dec.k1                      # skipped ⇒ K1 everywhere
        in_m_acc = jnp.zeros(n, jnp.bool_)
        n_surv_total = jnp.int32(0)
        slots = jnp.int32(0)
        max_span = jnp.int32(0)
        for seg_mask, n_seg, chunk_fn in segments:
            if n_seg == 0:
                continue
            skip_seg = dec.skip if seg_mask is None else dec.skip | ~seg_mask
            rank, n_surv = three_branch.survivor_rank(skip_seg)
            n_chunks = max(1, -(-n_seg // capacity))
            surv_idx = three_branch.compact_survivor_indices(
                rank, skip_seg, n_chunks * capacity)
            if self.balance == "tiles":
                max_span = jnp.maximum(
                    max_span,
                    self._max_chunk_span(surv_idx, n_chunks, capacity,
                                         word_ids=word_ids))
            new_topics, in_m_seg = three_branch.run_survivor_chunks(
                surv_idx, n_surv, new_topics,
                capacity=capacity, n_chunks=n_chunks, sample_chunk=chunk_fn)
            in_m_acc = in_m_acc | in_m_seg
            n_surv_total = n_surv_total + n_surv
            slots = slots + three_branch.chunk_slots(n_surv, capacity, n_seg)

        # -- the update: the SAME compacted changed-token scatter engine as
        # the dense pipeline, aimed straight at the densified matrices
        # (their sampling consumers are done), which then land back on the
        # packed state at matrix shape.
        d_new, w_new, colsum = scatter_changed_deltas(
            topics, new_topics, doc_ids, word_ids, mask, capacity=capacity,
            D=d_dense, W=w_int, colsum=colsum)

        d_packed, w_head, w_tail, overflow = self._repack_counts(
            d_new, w_new, overflow)

        st = branch_stats(dec.skip, in_m_acc, new_topics, topics, dec.k1,
                          slots)
        from repro.lda.model import SparseLDAState
        new_state = SparseLDAState(
            topics=new_topics, D=d_packed, W_head=w_head, W_tail=w_tail,
            colsum=colsum, overflow=overflow, key=key,
            iteration=iteration + 1)
        return new_state, st, n_surv_total, max_span


# ---------------------------------------------------------------------------
# out-of-core streaming (corpus_residency="streamed", DESIGN.md SS10)
# ---------------------------------------------------------------------------

# Device bytes per resident token: word + doc + mask + topic, int32 each.
# The residency auto-policy prices the RESIDENT representation with this.
STREAM_BYTES_PER_TOKEN = 16

# Device bytes per token of a STREAMED shard window: the resident
# quadruple plus the staged epoch-uniform slice (f32) that ships with
# the prefetch. The shard planner prices the double buffer with this.
STREAM_WINDOW_BYTES_PER_TOKEN = STREAM_BYTES_PER_TOKEN + 4

# Fraction of the device budget the double-buffered token window may use;
# the rest stays free for D/W/Ŵ, the epoch delta matrices, and dispatch
# temporaries (budget math in DESIGN.md SS10).
STREAM_WINDOW_BUDGET_FRACTION = 4

# The canonical checkpoint payload's mid-epoch extension keys
# (docs/API.md "Checkpoint payload schema"). Every backend that converts
# payloads must pass these through — a dropped key silently bypasses the
# mid-epoch restore guards.
STREAM_PAYLOAD_KEYS = ("stream_cursor", "stream_done_topics",
                       "stream_n_shards")


def plan_stream_shards(n_padded_tokens: int, budget_bytes: int | None, *,
                       multiple: int = 1, floor: int = 4) -> int:
    """Shard count so TWO shards' token buffers fit the window budget.

    The streaming window holds the resident shard plus the prefetched
    next shard (double buffer), each carrying 20 B/token (the token
    quadruple + the staged uniform slice), so the constraint is
    ``2 · 20B · ceil(N/S) <= budget / STREAM_WINDOW_BUDGET_FRACTION``.
    With no budget signal the floor (4 shards — the smallest count where
    streaming beats residency on token bytes) applies.
    """
    if n_padded_tokens <= 0:
        return 1
    shards = floor
    if budget_bytes:
        window = max(budget_bytes // STREAM_WINDOW_BUDGET_FRACTION, 1)
        shards = max(shards, -(-2 * STREAM_WINDOW_BYTES_PER_TOKEN
                               * n_padded_tokens // window))
    # never shard below one pad multiple per shard
    max_shards = max(n_padded_tokens // max(multiple, 1), 1)
    return int(min(shards, max_shards))


# one warning per process: auto-residency consults memory_stats() on every
# trainer build, and a backend without it (CPU) would otherwise warn each time
_MEMSTATS_WARNED = False


def resolves_to_disk(config) -> bool:
    """True iff ``config`` trains disk-native: residency "disk", or
    "auto" with a ``corpus_path`` (which resolves to "disk" before any
    budget probe — resolution table: docs/API.md). The shared predicate
    for the entry points that must pick the CorpusStore code path
    BEFORE a corpus exists to measure."""
    return config.corpus_residency == "disk" or (
        config.corpus_residency == "auto"
        and config.corpus_path is not None)


def resolve_residency(config, n_padded_tokens: int,
                      device=None) -> tuple[str, int]:
    """(residency, n_shards) for one (config, corpus) pair.

    ``corpus_residency="full"|"streamed"`` are honored as written;
    ``"auto"`` streams iff the estimated resident token bytes
    (``16B · N``) exceed the device budget — ``config.device_budget_bytes``
    when set, else half the device's reported ``bytes_limit``, else no
    signal and the corpus stays resident (CPU backends report no limit).
    """
    mode = config.corpus_residency
    if mode == "auto" and config.corpus_path is not None:
        # a corpus_path names a disk-native store; "auto" resolves to it
        # before any budget probe runs (resolution table: docs/API.md)
        mode = "disk"
    if mode == "disk":
        # disk-native: the CorpusStore's manifest fixes the shard count,
        # so there is nothing for the budget probe to plan (DESIGN.md SS14)
        return "disk", 0
    budget = config.device_budget_bytes
    if budget is None and mode != "full":
        # the device-derived budget feeds BOTH the auto policy and the
        # shard planner, so explicit "streamed" consults it too
        try:
            stats = (device or jax.devices()[0]).memory_stats() or {}
        except Exception as e:
            global _MEMSTATS_WARNED
            if not _MEMSTATS_WARNED:
                _MEMSTATS_WARNED = True
                warnings.warn(
                    "resolve_residency: device memory_stats() failed "
                    f"({type(e).__name__}: {e}); no device budget is "
                    "available, so corpus_residency='auto' resolves to "
                    "'full' and 'streamed' falls back to the minimum "
                    "shard count — set LDAConfig.device_budget_bytes to "
                    "make the residency decision explicit",
                    RuntimeWarning, stacklevel=2)
            stats = {}
        limit = stats.get("bytes_limit")
        budget = int(limit) // 2 if limit else None
    if mode == "auto":
        if budget is None:
            return "full", 1
        mode = "streamed" if (STREAM_BYTES_PER_TOKEN * n_padded_tokens
                              > budget) else "full"
    if mode == "full":
        return "full", 1
    if config.stream_shards is not None:
        return "streamed", max(int(config.stream_shards), 2)
    return "streamed", max(plan_stream_shards(
        n_padded_tokens, budget, multiple=config.tile_size), 2)


class PrefetchTimeout(TimeoutError):
    """The prefetch watchdog expired: a shard's host→device transfer did
    not complete within ``LDAConfig.stream_watchdog_seconds``. Raised
    from ``take()`` so the supervisor can restart instead of hanging."""


class _Prefetcher:
    """One-deep host→device prefetch queue (the background stream).

    ``submit`` starts moving the NEXT shard's buffers to the device on a
    worker thread while the current shard's dispatch runs; ``take``
    joins and returns the device tuple. jax.device_put is thread-safe;
    one worker keeps puts ordered.

    Failure handling: the worker retries a failed load ``retries`` times
    with exponential backoff before the exception is allowed to surface
    (a transient I/O hiccup never reaches the training loop), and
    ``take`` enforces an optional watchdog ``deadline_s`` — a hung
    transfer becomes a :class:`PrefetchTimeout` instead of a silent
    stall. Failures propagate ONLY from ``take`` (inside the epoch
    loop, where the supervisor can act); ``close`` drains and suppresses
    them — teardown of an already-failed pipeline must not raise again.
    """

    def __init__(self, *, retries: int = 2, backoff_s: float = 0.05,
                 deadline_s: float | None = None):
        self._ex = concurrent.futures.ThreadPoolExecutor(
            max_workers=1, thread_name_prefix="lda-stream-prefetch")
        self._fut = None
        self.retries = max(int(retries), 0)
        self.backoff_s = float(backoff_s)
        self.deadline_s = deadline_s

    def _attempt(self, fn, args):
        for attempt in range(self.retries + 1):
            try:
                return fn(*args)
            except Exception:
                if attempt == self.retries:
                    raise
                time.sleep(self.backoff_s * (2 ** attempt))

    def submit(self, fn, *args) -> None:
        assert self._fut is None, "prefetch queue is one deep"
        self._fut = self._ex.submit(self._attempt, fn, args)

    def take(self):
        fut, self._fut = self._fut, None
        if fut is None:
            return None
        try:
            return fut.result(timeout=self.deadline_s)
        except concurrent.futures.TimeoutError:
            fut.cancel()
            raise PrefetchTimeout(
                f"prefetch exceeded its {self.deadline_s}s watchdog "
                "deadline (stream_watchdog_seconds): transfer thread hung "
                "or host I/O stalled") from None

    def close(self) -> None:
        fut, self._fut = self._fut, None
        if fut is not None:
            fut.cancel()
            try:
                fut.result(timeout=1.0)
            except Exception:
                pass        # teardown never re-raises a pending failure
        self._ex.shutdown(wait=False)

    def __del__(self):
        # pipelines have no explicit teardown; reclaim the worker thread
        # when the owner is collected instead of leaking one per pipeline
        try:
            self._ex.shutdown(wait=False)
        except Exception:
            pass


@dataclasses.dataclass
class _EpochCarry:
    """Mid-epoch device/host state (exists only while an epoch is open).

    ``derived`` holds the iteration-start quantities every shard of the
    epoch samples against (Ŵ, word stats — plus the densified count
    mirrors for the hybrid pipeline); ``deltas`` accumulates the epoch's
    ±1 count moves so no shard ever observes another shard's updates
    (that deferral is what keeps streamed == resident bit-equal);
    ``old_topics`` stashes the epoch-start topics of completed shards so
    a mid-epoch checkpoint can reconstruct the sampling counts.
    """
    key_next: jax.Array
    u_host: np.ndarray             # the epoch's uniforms, host-staged
    derived: tuple
    deltas: tuple
    old_topics: list
    # device-side readbacks are DEFERRED (lists of device scalars /
    # pending topic buffers) so no per-shard host sync ever serializes
    # the dispatch queue; _flush() realizes them at the epoch close
    pending_topics: list = dataclasses.field(default_factory=list)
    stats_parts: list = dataclasses.field(default_factory=list)
    # paged-W mode only: the epoch's full-vocabulary dW accumulates
    # HOST-side (int64-safe int32 adds), fed by one-deep deferred
    # readbacks of each shard's (page_rows, K) scatter window
    dw_host: np.ndarray | None = None
    pending_dw: list = dataclasses.field(default_factory=list)
    n_surv: int = 0
    max_span: int = 0
    stat_sums: np.ndarray = dataclasses.field(
        default_factory=lambda: np.zeros(5, np.float64))

    def flush_stats(self) -> None:
        for n_surv, span, sums in self.stats_parts:
            self.n_surv += int(n_surv)
            self.max_span = max(self.max_span, int(span))
            self.stat_sums += np.asarray(sums, np.float64)
        self.stats_parts = []


@dataclasses.dataclass
class StreamState:
    """Training state of the streaming pipelines (host-orchestrated).

    Token-side state (topic assignments) lives HOST-side, one array per
    epoch shard; only the count matrices — ``counts`` is the dense
    ``(D, W, colsum)`` or the hybrid packed tuple — stay device-resident.
    ``cursor`` is the number of shards already sampled in the open epoch
    (0 between epochs); ``epoch`` carries the open epoch's derived
    quantities and accumulated deltas.
    """
    shard_topics: list
    counts: tuple
    key: jax.Array
    iteration: int
    cursor: int = 0
    epoch: _EpochCarry | None = None
    # paged-W mode only: the full (V, K) word-topic matrix lives HERE,
    # host-side; ``counts`` then carries only (D, colsum) (dense) or
    # (d_packed, colsum, overflow) (hybrid) and the device never holds
    # more than the active shard's W row window
    w_host: np.ndarray | None = None
    # paged-W mode only: the page endpoint the epoch loop pulls W row
    # windows from and pushes delta blocks to (lazily a HostPages over
    # this state; the PS trainer speaks the same verbs to owner shards)
    pages: "HostPages | None" = None

    @property
    def topics(self):
        """Host-side per-shard topics view (duck-types the device states
        for consumers that only read/block on .topics)."""
        return self.shard_topics


class HostPages:
    """The paged pipeline's W traffic, spoken as wire verbs.

    ``pull_page(lo, hi)`` yields the row window a shard samples
    against, ``push_page(lo, hi, delta)`` lands the shard's int32 delta
    block on the round accumulator, and ``finish_round()`` applies the
    accumulated round at the epoch close.  These are exactly the verbs
    the parameter-server client exposes (``repro.lda.ps.PSClient``), so
    the epoch loop never assumes W is resident — it speaks one
    pull/push/commit discipline whether the rows live in this process
    (here: ``StreamState.w_host`` plus the open epoch's ``dw_host``
    accumulator) or across sharded owners on a server.

    Pulls deliberately see only ROUND-START rows — pushes accumulate in
    ``dw_host`` and land at ``finish_round()`` — matching the server's
    committed-rows semantics; that deferral is what keeps streamed ==
    resident bit-equal.  Arrays are resolved through the state object at
    call time (not captured) because mid-epoch restores rebind
    ``w_host``/``dw_host`` wholesale.
    """

    def __init__(self, ss: StreamState):
        self._ss = ss

    def pull_page(self, lo: int, hi: int) -> np.ndarray:
        return self._ss.w_host[lo:hi]

    def push_page(self, lo: int, hi: int, delta: np.ndarray) -> None:
        self._ss.epoch.dw_host[lo:hi] += delta

    def finish_round(self) -> None:
        # int32 adds are exact and commutative, so this equals the
        # device-resident apply row for row
        self._ss.w_host += self._ss.epoch.dw_host


class StreamingPipeline(FusedPipeline):
    """The fused iteration, streamed one epoch shard at a time.

    Same sampling architecture as FusedPipeline (phase-1 skip, survivor
    compaction, cond-guarded phase-2 chunks, tile-scheduled dispatch
    under ``balance="tiles"`` with a TilePlan built per shard) but the
    token list never lives on the device whole: each iteration is an
    epoch over ``ShardedCorpus`` shards, with the next shard's
    (word, doc, mask, topics) buffers prefetched host→device on a
    background thread while the current shard's dispatch runs.

    Bit-equality with the resident path holds by construction:

      * the per-epoch uniforms are drawn ONCE at the RESIDENT padded
        length (the identical split + draw the resident iteration
        makes), staged to the host, and shipped back one shard slice at
        a time with the prefetch — every token sees the identical draw,
        the device never holds more than a slice, and the S× per-shard
        regeneration tax a naive re-draw would pay disappears;
      * every shard samples against the iteration-START ``D``/``W``/Ŵ —
        the epoch's ±1 moves accumulate in separate delta matrices and
        land in one donated apply at epoch end (integer adds commute, so
        the totals equal the resident path's in-place scatters);
      * chunking/tiling stay pure performance knobs (the same cond-
        guarded machinery, run shard-locally).

    Pinned by tests/test_streaming.py across dense × hybrid formats.
    """

    def __init__(self, stream, *, n_docs: int, n_words: int, config):
        from repro.lda.corpus import ShardedCorpus
        from repro.lda.storage import CorpusStore
        if getattr(config, "sampler", "three_branch") == "warp":
            raise ValueError(
                "sampler='warp' does not support corpus_residency="
                "'streamed' in this release: the MH doc proposal gathers "
                "topics of ARBITRARY same-doc tokens, which breaks the "
                "epoch-shard locality the streaming pipeline is built on "
                "(a shard would need every other shard's topics resident). "
                "Use corpus_residency='full' (or 'auto' on a device that "
                "fits the token list), or sampler='three_branch' for "
                "streamed training")
        # PAGED (disk-native) mode: the stream is a CorpusStore — token
        # bytes come from the file layer shard by shard, and W pages
        # through a per-shard row window instead of sitting device-
        # resident (DESIGN.md SS14)
        self.paged = isinstance(stream, CorpusStore)
        if not self.paged and not isinstance(stream, ShardedCorpus):
            raise ValueError(
                "StreamingPipeline takes a repro.lda.corpus.ShardedCorpus "
                "(build one with shard_stream(corpus, n_shards, "
                "multiple=config.tile_size)) or a repro.lda.storage."
                "CorpusStore (corpus_residency='disk')")
        if self.paged:
            # no host token arrays at all: the base class only ever uses
            # them for planning, and paged planning is manifest-driven
            super().__init__(None, None, None, n_docs=n_docs,
                             n_words=n_words, config=config,
                             n_tokens=stream.n_padded)
        else:
            flat = stream.word_ids.reshape(-1)[:stream.n_padded]
            flat_d = stream.doc_ids.reshape(-1)[:stream.n_padded]
            flat_m = stream.mask.reshape(-1)[:stream.n_padded]
            # host-side arrays: the base class only uses them for
            # planning; nothing here places the full stream on the device
            super().__init__(flat, flat_d, flat_m, n_docs=n_docs,
                             n_words=n_words, config=config)
        self.stream = stream
        L = stream.shard_len
        if not self._capacity_pinned:
            # working-set-bounded dispatch tiles measured fastest for the
            # per-shard dispatches (fig15's cache argument holds with or
            # without tile scheduling: a chunk's gathered rows must stay
            # resident) — benchmarked 0.59 -> 0.81x resident at K=32
            self.capacity = plan_tile_capacity(
                self.n_tokens, self.n_tokens, config.n_topics)
        self.capacity = min(self.capacity, L)
        if self.paged:
            # W page geometry from the manifest's word runs: every shard's
            # [first_word, last_word] run must fit one uniform window of
            # ``page_rows`` Ŵ rows (uniform so the shard jit compiles
            # once; the window BASE rides in as a traced scalar). Empty
            # trailing shards (last == first - 1) span 0 — clamped to 1.
            spans = np.maximum(
                np.asarray(stream.last_word, np.int64)
                - np.asarray(stream.first_word, np.int64) + 1, 1)
            self._page_rows = int(min(max(int(spans.max()), 1), n_words))
            self._page_base = np.minimum(
                np.maximum(np.asarray(stream.first_word, np.int64), 0),
                max(n_words - self._page_rows, 0))
        if self.balance == "tiles" and not self.paged:
            # per-shard tile planning (the _plan_tiles override deferred
            # to here): the word window must cover the widest run any
            # SHARD's tiles span, not the full stream's. Only the spans
            # are kept — whole plans would be dead host memory at scale.
            spans = [1]
            for s in range(stream.n_shards):
                real = int(stream.real_per_shard[s])
                if not real:
                    continue
                plan = balance_mod.build_tiles_from_word_ids(
                    stream.word_ids[s][:real], min(self.capacity, real))
                spans.append(plan.max_words_per_tile)
            self.win_words = plan_window(max(spans), n_words)
        self._begin_fn = None
        self._end_fn = None
        self._shard_cache: dict[tuple, Callable] = {}
        self._prefetch = _Prefetcher(
            deadline_s=getattr(config, "stream_watchdog_seconds", None))
        self.last_epoch_device_bytes = 0

    def _plan_tiles(self, word_ids) -> None:
        # no full-stream plan: per-shard plans are built (and win_words
        # set) once the stream is attached — one pass over the tokens
        self.win_words = self.n_words

    # -- state conversion ---------------------------------------------------

    def _split_topics(self, topics) -> list:
        st = self.stream
        total = st.n_shards * st.shard_len
        flat = np.zeros(total, np.int32)
        flat[:len(np.asarray(topics))] = np.asarray(topics, np.int32)
        return list(flat.reshape(st.n_shards, st.shard_len))

    def _counts_from_lda_state(self, state) -> tuple:
        colsum = jnp.sum(state.W, axis=0, dtype=jnp.int32)
        if self.paged:
            # W does NOT join the device-resident counts: it lives
            # host-side (StreamState.w_host) and pages through per-shard
            # row windows
            return (jnp.copy(state.D), colsum)
        return (jnp.copy(state.D), jnp.copy(state.W), colsum)

    def _counts_from_np(self, D: np.ndarray, W: np.ndarray) -> tuple:
        if self.paged:
            return (jnp.asarray(D),
                    jnp.asarray(W.sum(axis=0, dtype=np.int32)))
        return (jnp.asarray(D), jnp.asarray(W),
                jnp.asarray(W.sum(axis=0, dtype=np.int32)))

    def from_lda_state(self, state) -> StreamState:
        if isinstance(state, StreamState):
            return state        # resuming (possibly mid-epoch): no-op
        key = jax.random.wrap_key_data(jnp.copy(
            jax.random.key_data(state.key)))
        ss = StreamState(
            shard_topics=self._split_topics(state.topics),
            counts=self._counts_from_lda_state(state), key=key,
            iteration=int(state.iteration))
        if self.paged:
            ss.w_host = np.asarray(state.W, np.int32).copy()
        return ss

    def _require_boundary(self, ss: StreamState, what: str) -> None:
        if ss.cursor:
            raise ValueError(
                f"{what} needs an epoch boundary but {ss.cursor} of "
                f"{self.stream.n_shards} shards of the open epoch are "
                "already sampled: finish the epoch (run_fused) or "
                "checkpoint through stream_payload()")

    def to_lda_state(self, ss: StreamState):
        from repro.lda.model import LDAState
        self._require_boundary(ss, "to_lda_state")
        topics = np.concatenate(ss.shard_topics)[:self.n_tokens]
        if self.paged:
            D, _colsum = ss.counts
            # densifying to an LDAState is the one paged export that
            # re-uploads the full W — callers that only need a score or
            # a checkpoint use eval_llpt / stream_payload instead
            return LDAState(topics=jnp.asarray(topics), D=D,
                            W=jnp.asarray(ss.w_host), key=ss.key,
                            iteration=jnp.int32(ss.iteration))
        D, W, colsum = ss.counts
        return LDAState(topics=jnp.asarray(topics), D=D, W=W, key=ss.key,
                        iteration=jnp.int32(ss.iteration))

    # -- compiled pieces ----------------------------------------------------

    def _get_begin(self) -> Callable:
        if self._begin_fn is None:
            cfg, n = self.config, self.n_tokens
            paged = self.paged

            def begin(counts, key):
                if paged:
                    # paged counts carry no W: Ŵ and the word stats are
                    # recomputed per shard from the prefetched row window
                    # (row-identical math — see the paged shard_fn), so
                    # the epoch open is just the key split + the u draw,
                    # in the exact resident order
                    D, colsum = counts
                    key_next, sub = jax.random.split(key)
                    u = jax.random.uniform(sub, (n,), dtype=jnp.float32)
                    deltas = (jnp.zeros_like(D), jnp.zeros_like(colsum))
                    return key_next, u, (), deltas
                D, W, colsum = counts
                key_next, sub = jax.random.split(key)
                # the epoch's uniforms, drawn ONCE at the resident length
                # (bit-identical to the resident path's per-iteration u)
                # and immediately staged to the host: each shard's slice
                # rides back in with the prefetch, so the device never
                # holds more than one shard's worth between dispatches
                # and the S× regeneration tax disappears
                u = jax.random.uniform(sub, (n,), dtype=jnp.float32)
                W_hat = esca.compute_w_hat_from_colsum(W, colsum, cfg.beta)
                stats_w = three_branch.word_stats(W_hat, g=cfg.g,
                                                  alpha=cfg.alpha_)
                deltas = (jnp.zeros_like(D), jnp.zeros_like(W),
                          jnp.zeros_like(colsum))
                return key_next, u, (W_hat, stats_w), deltas

            self._begin_fn = jax.jit(begin)
        return self._begin_fn

    def _stage_u(self, u_dev) -> np.ndarray:
        """Device u → host staging buffer, padded to the stream extent
        (the extension slots' draws are inert — mask-0 tokens)."""
        st = self.stream
        total = st.n_shards * st.shard_len
        u = np.zeros(total, np.float32)
        u[:self.n_tokens] = np.asarray(u_dev)
        return u

    def _apply_epoch(self, counts: tuple, derived: tuple,
                     deltas: tuple) -> tuple:
        if self._end_fn is None:

            def end(counts, deltas):
                return tuple(c + d for c, d in zip(counts, deltas))

            # only counts can alias the outputs; the deltas are freed
            # naturally when the epoch carry drops
            self._end_fn = jax.jit(end, donate_argnums=(0,))
        return self._end_fn(counts, deltas)

    def _get_shard_fn(self, capacity: int, win_words: int) -> Callable:
        sig = (capacity, win_words)
        fn = self._shard_cache.get(sig)
        if fn is not None:
            return fn
        cfg = self.config
        st = self.stream
        L, n = st.shard_len, self.n_tokens
        n_chunks = max(1, -(-L // capacity))
        track_span = self.balance == "tiles"
        if self.paged:
            P, V = self._page_rows, self.n_words

            def paged_fn(u, base, lo, topics_s, word_s, doc_s, mask_s,
                         w_win, counts, derived, deltas):
                D, colsum = counts
                # iteration-START Ŵ + word stats, recomputed from the
                # shard's prefetched W row window: both are row-wise, so
                # the window rows are bitwise the rows the resident epoch
                # open computes, and every downstream gather goes through
                # window-LOCAL word ids (clip only rebases the inert pad
                # slots of empty trailing shards)
                W_hat = esca.compute_w_hat_from_colsum(
                    w_win, colsum, cfg.beta, n_words=V)
                stats_w = three_branch.word_stats(W_hat, g=cfg.g,
                                                  alpha=cfg.alpha_)
                word_l = jnp.clip(word_s - base, 0, P - 1).astype(jnp.int32)
                dec = three_branch.skip_phase(u, word_l, doc_s, D, stats_w,
                                              g=cfg.g, alpha=cfg.alpha_)
                rank, n_surv = three_branch.survivor_rank(dec.skip)
                surv_idx = three_branch.compact_survivor_indices(
                    rank, dec.skip, n_chunks * capacity)
                sample_chunk = self._dense_chunk_sampler(
                    u, word_l, doc_s, D, W_hat, stats_w.k[:, 0],
                    win_words=V, n_stream=L)
                new_topics, in_m = three_branch.run_survivor_chunks(
                    surv_idx, n_surv, dec.k1, capacity=capacity,
                    n_chunks=n_chunks, sample_chunk=sample_chunk)
                dD, dw_win, dcs = scatter_changed_deltas(
                    topics_s, new_topics, doc_s, word_l, mask_s,
                    capacity=capacity, D=deltas[0],
                    W=jnp.zeros((P, cfg.n_topics), jnp.int32),
                    colsum=deltas[1])
                sums = _shard_stat_sums(
                    lo, n, dec, in_m, new_topics, topics_s,
                    three_branch.chunk_slots(n_surv, capacity, L))
                return (new_topics, (dD, dcs), dw_win, n_surv,
                        jnp.int32(0), sums)

            fn = jax.jit(paged_fn, donate_argnums=(3, 7, 10))
            self._shard_cache[sig] = fn
            return fn

        def shard_fn(u, lo, topics_s, word_s, doc_s, mask_s, counts,
                     derived, deltas):
            D, _W, _colsum = counts
            W_hat, stats_w = derived
            dec = three_branch.skip_phase(u, word_s, doc_s, D, stats_w,
                                          g=cfg.g, alpha=cfg.alpha_)
            rank, n_surv = three_branch.survivor_rank(dec.skip)
            surv_idx = three_branch.compact_survivor_indices(
                rank, dec.skip, n_chunks * capacity)
            max_span = self._max_chunk_span(
                surv_idx, n_chunks, capacity, word_ids=word_s,
                n_stream=L) if track_span else jnp.int32(0)
            sample_chunk = self._dense_chunk_sampler(
                u, word_s, doc_s, D, W_hat, stats_w.k[:, 0],
                win_words=win_words, n_stream=L)
            new_topics, in_m = three_branch.run_survivor_chunks(
                surv_idx, n_surv, dec.k1, capacity=capacity,
                n_chunks=n_chunks, sample_chunk=sample_chunk)
            deltas = scatter_changed_deltas(
                topics_s, new_topics, doc_s, word_s, mask_s,
                capacity=capacity, D=deltas[0], W=deltas[1],
                colsum=deltas[2])
            sums = _shard_stat_sums(
                lo, n, dec, in_m, new_topics, topics_s,
                three_branch.chunk_slots(n_surv, capacity, L))
            return new_topics, deltas, n_surv, max_span, sums

        fn = jax.jit(shard_fn, donate_argnums=(2, 8))
        self._shard_cache[sig] = fn
        return fn

    # -- the epoch loop -----------------------------------------------------

    def _load_shard_slices(self, s: int) -> tuple:
        """Host-side (word, doc, mask) slices for one shard, self-checked.

        Under ``config.selfcheck`` (or an armed chaos plan) the slice
        bytes are verified against the stream's per-shard crc32 before
        they reach the device — silent host-buffer corruption surfaces
        as a restartable :class:`ShardCorruptionError` at the load, not
        as a poisoned model three epochs later.

        In paged (disk-native) mode the load IS a file read:
        ``CorpusStore.read_shard`` owns the crc32 check (unconditional
        there) and the chaos fault hooks, so this method only routes.
        """
        st = self.stream
        if self.paged:
            return st.read_shard(s, _chaos=True)
        arrays = (st.word_ids[s], st.doc_ids[s], st.mask[s])
        if chaos.armed():
            chaos.io_fault(s)
            arrays = chaos.corrupt_arrays(s, arrays)
        if getattr(self.config, "selfcheck", False) or chaos.armed():
            want = int(st.shard_checksums[s])
            got = int(st.slice_checksum(*arrays))
            if got != want:
                raise invariants.ShardCorruptionError(
                    f"stream shard {s} failed its crc32 self-check "
                    f"(expected {want:#010x}, got {got:#010x}): host "
                    "shard bytes corrupted in flight — restore from the "
                    "newest checkpoint")
        return arrays

    def _pages(self, ss: StreamState) -> HostPages:
        """The state's W page endpoint (paged mode only), created lazily
        so every StreamState construction site — init, boundary restore,
        mid-epoch restore — gets one without ceremony."""
        if ss.pages is None:
            ss.pages = HostPages(ss)
        return ss.pages

    def _put_shard(self, s: int, topics_host, u_host, pages=None):
        word_s, doc_s, mask_s = self._load_shard_slices(s)
        L = self.stream.shard_len
        out = (jnp.asarray(word_s), jnp.asarray(doc_s),
               jnp.asarray(mask_s), jnp.asarray(topics_host),
               jnp.asarray(u_host[s * L:(s + 1) * L]))
        if self.paged:
            # the shard's W row window rides the same worker-thread put
            # as the token buffers: the device only ever holds the
            # active + prefetched windows, never the full (V, K) matrix
            b = int(self._page_base[s])
            out = out + (jnp.asarray(
                pages.pull_page(b, b + self._page_rows)),)
        return out

    def _open_epoch(self, ss: StreamState) -> StreamState:
        key_next, u_dev, derived, deltas = self._get_begin()(ss.counts,
                                                             ss.key)
        ss.epoch = _EpochCarry(key_next=key_next,
                               u_host=self._stage_u(u_dev),
                               derived=derived, deltas=deltas,
                               old_topics=[])
        if self.paged:
            ss.epoch.dw_host = np.zeros(
                (self.n_words, self.config.n_topics), np.int32)
        return ss

    def _drain_dw(self, ss: StreamState) -> None:
        """Push deferred per-shard dW window readbacks through the page
        endpoint onto the round accumulator (paged mode only)."""
        ep, pages = ss.epoch, self._pages(ss)
        while ep.pending_dw:
            b, dw = ep.pending_dw.pop(0)
            pages.push_page(b, b + self._page_rows, np.asarray(dw))

    def _close_epoch(self, ss: StreamState) -> StreamState:
        ep = ss.epoch
        if self.paged:
            self._drain_dw(ss)
        if getattr(self.config, "selfcheck", False):
            self._selfcheck_deltas(ep.deltas, ss.iteration,
                                   dw_host=ep.dw_host)
        ss.counts = self._apply_epoch(ss.counts, ep.derived, ep.deltas)
        if self.paged:
            # the epoch's queued W moves commit through the page endpoint
            self._pages(ss).finish_round()
        ss.key = ep.key_next
        ss.iteration += 1
        ss.cursor = 0
        ss.epoch = None
        if getattr(self.config, "selfcheck", False):
            self._selfcheck_counts(ss)
        return ss

    # -- count-invariant tripwires (config.selfcheck, invariants.py) --------

    def _selfcheck_deltas(self, deltas: tuple, iteration: int,
                          dw_host=None) -> None:
        if self.paged:
            # selfcheck is the one paged path that re-uploads the full
            # dW (a debug mode; the training path never does)
            dD, dcs = deltas
            invariants.check_delta_conservation(
                dD, jnp.asarray(dw_host), dcs,
                where=f"epoch {iteration} close (deltas)")
            return
        dD, dW, dcs = deltas
        invariants.check_delta_conservation(
            dD, dW, dcs, where=f"epoch {iteration} close (deltas)")

    def _selfcheck_counts(self, ss: StreamState) -> None:
        if self.paged:
            D, colsum = ss.counts
            invariants.check_dense_counts(
                D, jnp.asarray(ss.w_host), colsum,
                n_tokens=self.stream.n_tokens,
                where=f"epoch {ss.iteration} close (counts)")
            return
        D, W, colsum = ss.counts
        invariants.check_dense_counts(
            D, W, colsum, n_tokens=self.stream.n_tokens,
            where=f"epoch {ss.iteration} close (counts)")

    def selfcheck(self, ss) -> None:
        # the epoch close already ran the tripwires on this state; the
        # chunk-boundary call the resident pipelines need is a no-op here
        pass

    def _advance(self, ss: StreamState,
                 max_shards: int | None = None) -> StreamState:
        """Sample shards ``cursor..stop`` of the open epoch (opening one
        as needed) without closing it. The shard at ``cursor`` computes
        while the shard at ``cursor+1`` prefetches — the double buffer.
        """
        st = self.stream
        if ss.epoch is None:
            ss = self._open_epoch(ss)
        stop = st.n_shards if max_shards is None \
            else min(st.n_shards, ss.cursor + max_shards)
        if ss.cursor >= stop:
            return ss
        ep = ss.epoch
        pages = self._pages(ss) if self.paged else None
        fn = self._get_shard_fn(self.capacity, self.win_words)
        self._prefetch.take()       # drop any stale prefetch
        current = self._put_shard(ss.cursor, ss.shard_topics[ss.cursor],
                                  ep.u_host, pages)
        while ss.cursor < stop:
            s = ss.cursor
            if chaos.armed():
                chaos.shard_event(ss.iteration, s)
            if s + 1 < stop:
                self._prefetch.submit(self._put_shard, s + 1,
                                      ss.shard_topics[s + 1], ep.u_host,
                                      pages)
            if self.paged:
                word_s, doc_s, mask_s, topics_s, u_s, w_win = current
                new_t, ep.deltas, dw_win, n_surv, span, sums = fn(
                    u_s, jnp.int32(int(self._page_base[s])),
                    jnp.int32(s * st.shard_len), topics_s, word_s,
                    doc_s, mask_s, w_win, ss.counts, ep.derived,
                    ep.deltas)
                # the shard's dW window reads back one-deep deferred,
                # exactly like the topics — no per-shard host sync
                ep.pending_dw.append((int(self._page_base[s]), dw_win))
                if len(ep.pending_dw) > 1:
                    b_prev, dw_prev = ep.pending_dw.pop(0)
                    pages.push_page(b_prev, b_prev + self._page_rows,
                                    np.asarray(dw_prev))
            else:
                word_s, doc_s, mask_s, topics_s, u_s = current
                w_win = dw_win = None
                new_t, ep.deltas, n_surv, span, sums = fn(
                    u_s, jnp.int32(s * st.shard_len), topics_s, word_s,
                    doc_s, mask_s, ss.counts, ep.derived, ep.deltas)
            if self.last_epoch_device_bytes == 0:
                # every buffer shape is static, so one measurement per
                # pipeline suffices; .nbytes reads metadata only — no
                # transfer, no sync, no pipeline bubble
                window = (word_s, doc_s, mask_s, new_t, u_s)
                if self.paged:
                    window = window + (w_win, dw_win)
                self.last_epoch_device_bytes = self._device_bytes(
                    ss, window)
            ep.old_topics.append(ss.shard_topics[s])
            ep.stats_parts.append((n_surv, span, sums))
            # one-deep deferred D2H: shard s's topics read back while
            # shard s+1's dispatch is already enqueued — no bubble
            ep.pending_topics.append((s, new_t))
            if len(ep.pending_topics) > 1:
                s_prev, t_prev = ep.pending_topics.pop(0)
                ss.shard_topics[s_prev] = np.asarray(t_prev)
            ss.cursor += 1
            current = self._prefetch.take()
        while ep.pending_topics:
            s_prev, t_prev = ep.pending_topics.pop(0)
            ss.shard_topics[s_prev] = np.asarray(t_prev)
        if self.paged:
            self._drain_dw(ss)
        return ss

    def note_survivors(self, n_surv, decay: float = 0.7) -> None:
        super().note_survivors(n_surv, decay)
        if not self._capacity_pinned:
            self.capacity = plan_tile_capacity(
                self._surv_ema, self.n_tokens, self.config.n_topics)
        self.capacity = min(self.capacity, self.stream.shard_len)

    def note_spans(self, spans) -> None:
        if self.paged:
            # paged dispatch already gathers through the shard's window-
            # local ids; the tiled kernels stay off (win_words == V), so
            # span feedback must never shrink the window
            return
        super().note_spans(spans)

    def _n_real_tokens(self) -> int:
        return self.stream.n_tokens

    def run_shards(self, ss: StreamState,
                   n_shards: int = 1) -> StreamState:
        """Advance up to ``n_shards`` shards of the current epoch WITHOUT
        closing it — the mid-epoch stepping surface. A state left mid-
        epoch checkpoints through ``stream_payload`` and resumes through
        ``state_from_stream_payload`` (or ``run_fused``, whose first
        epoch finishes the open one) bit-identically."""
        return self._advance(ss, max_shards=max(int(n_shards), 0))

    def _run_epoch(self, ss: StreamState):
        """One full epoch (resuming an open one at ``ss.cursor``).

        Returns (state, n_surv_total, max_span, stat_means)."""
        ss = self._advance(ss)
        ep = ss.epoch
        ep.flush_stats()
        n_surv, span = ep.n_surv, ep.max_span
        means = ep.stat_sums / max(self.n_tokens, 1)
        return self._close_epoch(ss), n_surv, span, means

    def step(self, ss: StreamState):
        ss, stats, n_surv = self.run_fused(ss, 1)
        squeeze = lambda t: jax.tree.map(lambda x: x[0], t)
        return ss, squeeze(stats), squeeze(n_surv)

    def run_fused(self, ss: StreamState, n_iters: int, replan: bool = True):
        """n_iters epochs of shard-streamed training.

        Mirrors FusedPipeline.run_fused's return contract ((state,
        stacked stats, survivor counts) with a leading (n_iters,) axis)
        so the boundary-chunked trainer driver cannot tell the paths
        apart. Between epochs the survivor EMA re-plans the shard-local
        chunk capacity (and the tile window under ``balance="tiles"``) —
        the same hysteresis as the resident planner.
        """
        surv_rows, span_rows, mean_rows = [], [], []
        for _ in range(int(n_iters)):
            ss, n_surv, span, means = self._run_epoch(ss)
            surv_rows.append(n_surv)
            span_rows.append(span)
            mean_rows.append(means)
        if replan and surv_rows:
            # feed EPOCH-total survivors (not per-shard) so the EMA sees
            # the same signal as the resident planner
            self.note_survivors(np.asarray(surv_rows, np.float64))
            if self.balance == "tiles":
                self.note_spans(span_rows)
        m = np.asarray(mean_rows, np.float32).reshape(-1, 5)
        stats = three_branch.ThreeBranchStats(
            frac_skipped=m[:, 0], frac_m_final=m[:, 1],
            frac_unchanged=m[:, 2], frac_at_max=m[:, 3],
            frac_q_branch=np.zeros(m.shape[0], np.float32),
            frac_phase2_slots=np.minimum(m[:, 4], 1.0))
        return ss, stats, np.asarray(surv_rows, np.int64)

    # -- measured memory ----------------------------------------------------

    def _device_bytes(self, ss: StreamState, current: tuple) -> int:
        """Measured live device bytes at the streaming steady state:
        resident counts + the open epoch's derived/delta buffers + BOTH
        token windows (current shard + prefetched shard). In-dispatch
        temporaries are excluded — exactly as they are for the resident
        path's accounting (``FusedPipeline`` state + token buffers)."""
        total = sum(int(a.nbytes) for a in jax.tree.leaves(ss.counts))
        if ss.epoch is not None:
            total += sum(int(a.nbytes)
                         for a in jax.tree.leaves((ss.epoch.derived,
                                                   ss.epoch.deltas)))
        total += 2 * sum(int(a.nbytes) for a in current)
        return total

    # -- the serving export hook (bounded-staleness view, serve/refresh.py) --

    def serving_counts(self, ss: StreamState) -> tuple:
        """(W, cursor, n_shards): a dense host W of the CURRENT view.

        Mid-epoch this is ``W0 + ΔW`` — the epoch-start counts plus the
        already-sampled shards' accumulated moves, both device-resident
        anyway, so the export costs one add + one D2H. The un-sampled
        shards' moves are the only thing missing: staleness is bounded by
        ``(n_shards - cursor)/n_shards`` of one epoch. Integer adds make
        the cursor==n_shards view bitwise-equal to the counts the epoch
        close is about to apply, and the boundary view (cursor==0) IS the
        exact counts — which is why a serving swap at a boundary equals
        freezing a boundary checkpoint (pinned in
        tests/test_serve_service.py).
        """
        if self.paged:
            # paged W already lives host-side; mid-epoch the deferred dW
            # windows were drained when _advance returned, so w + dw IS
            # the current view — no device traffic at all
            if ss.epoch is None or ss.cursor == 0:
                return (ss.w_host.astype(np.int32, copy=True), 0,
                        self.stream.n_shards)
            return ((ss.w_host + ss.epoch.dw_host).astype(np.int32),
                    int(ss.cursor), self.stream.n_shards)
        if ss.epoch is None or ss.cursor == 0:
            return (np.asarray(ss.counts[1], np.int32), 0,
                    self.stream.n_shards)
        W = np.asarray(ss.counts[1] + ss.epoch.deltas[1], np.int32)
        return W, int(ss.cursor), self.stream.n_shards

    # -- checkpoints (mid-epoch capable) ------------------------------------

    def stream_payload(self, ss: StreamState) -> dict:
        """Canonical checkpoint payload, epoch-boundary or mid-epoch.

        At a boundary this is exactly the engine's canonical payload. A
        mid-epoch save adds the flat ``stream_cursor`` /
        ``stream_done_topics`` keys (docs/API.md "Checkpoint payload
        schema"): ``topics_global`` rewinds to the EPOCH-START topics
        (what the open epoch's counts derive from) and
        ``stream_done_topics`` carries the already-sampled shards' new
        topics, so a restore re-derives counts, Ŵ, and the accumulated
        deltas and continues bit-identically.
        """
        st = self.stream
        n_real = st.n_tokens
        key = np.asarray(jax.random.key_data(ss.key))
        if ss.cursor == 0:
            topics = np.concatenate(ss.shard_topics)[:n_real]
            return {"topics_global": topics, "key": key,
                    "iteration": int(ss.iteration)}
        start = np.concatenate(
            list(ss.epoch.old_topics) + ss.shard_topics[ss.cursor:])[:n_real]
        n_done = int(min(ss.cursor * st.shard_len, n_real))
        done = np.concatenate(ss.shard_topics[:ss.cursor])[:n_done]
        return {"topics_global": start, "key": key,
                "iteration": int(ss.iteration),
                "stream_cursor": np.int64(ss.cursor),
                "stream_done_topics": done.astype(np.int32),
                "stream_n_shards": np.int64(st.n_shards)}

    def _np_counts(self, topics_flat: np.ndarray, lo: int, hi: int):
        """Host count histograms over padded-stream slots [lo, hi).

        Folds shard by shard, so in paged mode the token arrays come
        through ``read_shard`` one slice at a time (never the whole
        stream in host RAM) — the masked int adds are order-independent,
        so the fold equals the flat histogram exactly. Both call sites
        pass shard-aligned ranges.
        """
        st = self.stream
        L = st.shard_len
        K = self.config.n_topics
        D = np.zeros((self.n_docs, K), np.int32)
        W = np.zeros((self.n_words, K), np.int32)
        for s in range(lo // L, min(-(-hi // L), st.n_shards)):
            if self.paged:
                w, d, m = st.read_shard(s)
            else:
                w, d, m = st.word_ids[s], st.doc_ids[s], st.mask[s]
            a = s * L
            sl = slice(max(lo - a, 0), min(hi - a, L))
            t = topics_flat[a + sl.start:a + sl.stop]
            np.add.at(D, (d[sl], t), m[sl].astype(np.int32))
            np.add.at(W, (w[sl], t), m[sl].astype(np.int32))
        return D, W

    def state_from_stream_payload(self, payload: dict) -> StreamState:
        """Rebuild a StreamState (possibly mid-epoch) from a canonical
        payload. Everything beyond the payload is derived state: counts
        from the epoch-start topics, Ŵ/stats by re-running the epoch
        open, the accumulated deltas from (old, done-new) histograms."""
        st = self.stream
        n_real = st.n_tokens
        tg = np.asarray(payload["topics_global"], np.int32)
        if tg.shape[0] != n_real:
            raise ValueError(
                f"checkpoint topics_global has {tg.shape[0]} entries but "
                f"the corpus holds {n_real} tokens: the checkpoint belongs "
                "to a different corpus")
        sn = payload.get("stream_n_shards")
        if sn is not None and int(sn) != st.n_shards:
            raise ValueError(
                f"checkpoint was saved mid-epoch with {int(sn)} stream "
                f"shards but this pipeline streams {st.n_shards}: the "
                "shard grid must match to resume mid-epoch (re-save the "
                "checkpoint at an epoch boundary to re-shard)")
        total = st.n_shards * st.shard_len
        flat = np.zeros(total, np.int32)
        flat[:n_real] = tg
        D0, W0 = self._np_counts(flat, 0, total)
        key = jax.random.wrap_key_data(jnp.asarray(payload["key"]))
        ss = StreamState(
            shard_topics=list(flat.reshape(st.n_shards, st.shard_len)),
            counts=self._counts_from_np(D0, W0),
            key=key, iteration=int(payload["iteration"]))
        if self.paged:
            ss.w_host = W0
        cursor = int(payload.get("stream_cursor", 0))
        if cursor == 0:
            return ss
        if not 0 < cursor <= st.n_shards:
            raise ValueError(
                f"stream_cursor={cursor} out of range for {st.n_shards} "
                "shards: the checkpoint was written for a different "
                "stream sharding (stream_shards must match to resume "
                "mid-epoch)")
        n_done = int(min(cursor * st.shard_len, n_real))
        done = np.asarray(payload["stream_done_topics"], np.int32)
        if done.shape[0] != n_done:
            raise ValueError(
                f"stream_done_topics has {done.shape[0]} entries; cursor "
                f"{cursor} implies {n_done}: inconsistent mid-epoch payload")
        ss = self._open_epoch(ss)
        new_flat = flat.copy()
        new_flat[:n_done] = done
        hi = cursor * st.shard_len
        Dn, Wn = self._np_counts(new_flat, 0, hi)
        Do, Wo = self._np_counts(flat, 0, hi)
        if self.paged:
            ss.epoch.deltas = (jnp.asarray(Dn - Do),
                               jnp.asarray((Wn - Wo).sum(axis=0,
                                                         dtype=np.int32)))
            ss.epoch.dw_host = (Wn - Wo).astype(np.int32)
        else:
            ss.epoch.deltas = (jnp.asarray(Dn - Do), jnp.asarray(Wn - Wo),
                               jnp.asarray((Wn - Wo).sum(axis=0,
                                                         dtype=np.int32)))
        ss.epoch.old_topics = list(
            flat.reshape(st.n_shards, st.shard_len)[:cursor])
        for s in range(cursor):
            ss.shard_topics[s] = new_flat.reshape(
                st.n_shards, st.shard_len)[s]
        ss.cursor = cursor
        return ss

    # -- out-of-core evaluation (Eq 5 folded over shards, DESIGN.md SS14) ---

    def _eval_parts(self, ss: StreamState) -> tuple:
        """(D, W_full_or_None, colsum) for the shard-folded evaluator;
        W is None exactly when it pages (paged mode)."""
        if self.paged:
            D, colsum = ss.counts
            return D, None, colsum
        D, W, colsum = ss.counts
        return D, W, colsum

    def eval_llpt(self, ss: StreamState) -> float:
        """LLPT (Eq 5) without ever uploading the full token list.

        Folds ``core.llpt.token_ll`` over the epoch shards — in paged
        mode each dispatch sees only the shard's token slice plus its W
        row window (window-local ids; phi rows enter through gathers, so
        per-token values are identical to the full-matrix call) — then
        feeds the assembled per-token vector through the SAME compiled
        ``reduce_ll`` the resident ``llpt`` uses. Same values through
        the same reduction ⇒ bitwise-equal score (pinned in
        tests/test_streaming.py).
        """
        from repro.core import llpt as llpt_mod
        self._require_boundary(ss, "eval_llpt")
        st, cfg = self.stream, self.config
        L = st.shard_len
        D, W_full, colsum = self._eval_parts(ss)
        colsum32 = jnp.asarray(colsum).astype(jnp.float32)
        parts = []
        for s in range(st.n_shards):
            if self.paged:
                w_s, d_s, _m = st.read_shard(s)
                b = int(self._page_base[s])
                w_win = jnp.asarray(self._pages(ss).pull_page(
                    b, b + self._page_rows))
                v = jnp.asarray(
                    np.clip(w_s - b, 0, self._page_rows - 1)
                    .astype(np.int32))
            else:
                w_s, d_s = st.word_ids[s], st.doc_ids[s]
                w_win = W_full
                v = jnp.asarray(w_s)
            ll = llpt_mod.token_ll(
                v, jnp.asarray(d_s), D, w_win, colsum32,
                alpha=cfg.alpha_, beta=cfg.beta, n_words=self.n_words,
                tile_size=cfg.tile_size)
            parts.append(np.asarray(ll))
        ll_all = np.concatenate(parts)[:self.n_tokens]
        # by the stream invariant the real tokens are exactly the first
        # n_tokens padded slots, so the resident mask is synthesizable
        mask = (np.arange(self.n_tokens, dtype=np.int64)
                < st.n_tokens).astype(np.int32)
        return float(llpt_mod.reduce_ll(jnp.asarray(ll_all),
                                        jnp.asarray(mask)))


def _shard_stat_sums(lo, n, dec, in_m, new_topics, old_topics, slots):
    """Per-shard stat SUMS over slots that exist in the resident stream
    (global index < n), so the epoch totals divide to the same fractions
    the resident pipeline reports; the last is the exact-draw slots phase
    2 ran in the shard (``chunk_slots``)."""
    L = new_topics.shape[0]
    valid = (lo + jnp.arange(L)) < n
    f32 = jnp.float32

    def s(x):
        return jnp.sum(jnp.where(valid, x, False).astype(f32))

    return jnp.stack([s(dec.skip), s(dec.skip | in_m),
                      s(new_topics == old_topics), s(new_topics == dec.k1),
                      slots.astype(f32)])


class StreamingHybridPipeline(StreamingPipeline):
    """Epoch-shard streaming over the hybrid sparse live state.

    The at-rest state between epochs stays packed (packed-ELL D +
    HybridW + colsum + overflow tripwire — the same tuple
    SparseLDAState carries); the epoch open densifies it ONCE into the
    integer mirrors every shard samples against (exactly what the
    resident HybridFusedPipeline does once per iteration, so the
    trajectory is bit-equal to it), and the epoch close applies the
    accumulated deltas and repacks with the same sorted-slot machinery.
    Note the densified mirrors are epoch-resident here (the resident
    pipeline holds them only inside its dispatch) — streaming's token
    savings pay for a transient dense count mirror; the measured
    accounting in ``_device_bytes`` includes them.
    """

    def __init__(self, stream, *, n_docs: int, n_words: int, config,
                 corpus):
        super().__init__(stream, n_docs=n_docs, n_words=n_words,
                         config=config)
        from repro.lda.model import HybridLayout
        self.layout = HybridLayout.build(corpus, config)

    # -- state conversion ---------------------------------------------------

    def _counts_from_lda_state(self, state) -> tuple:
        lay = self.layout
        colsum = jnp.sum(state.W, axis=0, dtype=jnp.int32)
        if self.paged:
            # paged hybrid NEVER packs W: the host mirror (w_host) is
            # the at-rest W, so only the document side stays packed on
            # device (DESIGN.md SS14)
            return (lay.pack_d(state.D), colsum, jnp.int32(0))
        w_head, w_tail = lay.split_w(state.W)
        return (lay.pack_d(state.D), w_head, w_tail, colsum, jnp.int32(0))

    def _counts_from_np(self, D: np.ndarray, W: np.ndarray) -> tuple:
        lay = self.layout
        colsum = jnp.asarray(W.sum(axis=0, dtype=np.int32))
        if self.paged:
            return (lay.pack_d(jnp.asarray(D)), colsum, jnp.int32(0))
        w_head, w_tail = lay.split_w(jnp.asarray(W))
        return (lay.pack_d(jnp.asarray(D)), w_head, w_tail, colsum,
                jnp.int32(0))

    def to_lda_state(self, ss: StreamState):
        from repro.lda.model import LDAState
        self._require_boundary(ss, "to_lda_state")
        topics = np.concatenate(ss.shard_topics)[:self.n_tokens]
        if self.paged:
            d_packed, _colsum, _overflow = ss.counts
            return LDAState(
                topics=jnp.asarray(topics),
                D=sparse.densify_rows(d_packed, self.layout.n_topics),
                W=jnp.asarray(ss.w_host),
                key=ss.key, iteration=jnp.int32(ss.iteration))
        d_packed, w_head, w_tail, _colsum, _overflow = ss.counts
        return LDAState(
            topics=jnp.asarray(topics),
            D=sparse.densify_rows(d_packed, self.layout.n_topics),
            W=self.layout.densify_w(w_head, w_tail),
            key=ss.key, iteration=jnp.int32(ss.iteration))

    def overflow_count(self, ss: StreamState) -> int:
        """The packed-update tripwire (0 by the capacity-bound design)."""
        return int(ss.counts[2] if self.paged else ss.counts[4])

    def serving_counts(self, ss: StreamState) -> tuple:
        """Hybrid serving export: the epoch-resident densified W mirror
        plus the accumulated ΔW mid-epoch; densify the packed state at a
        boundary. Same staleness/bitwise contract as the dense pipeline.
        Paged mode serves straight from the host mirror (format-free)."""
        if self.paged:
            return StreamingPipeline.serving_counts(self, ss)
        if ss.epoch is None or ss.cursor == 0:
            _d, w_head, w_tail, _cs, _ov = ss.counts
            W = self.layout.densify_w(w_head, w_tail)
            return np.asarray(W, np.int32), 0, self.stream.n_shards
        _d_dense, w_int, _W_hat, _stats = ss.epoch.derived
        W = np.asarray(w_int + ss.epoch.deltas[1], np.int32)
        return W, int(ss.cursor), self.stream.n_shards

    def _selfcheck_counts(self, ss: StreamState) -> None:
        if self.paged:
            _d_packed, colsum, overflow = ss.counts
        else:
            _d_packed, _w_head, _w_tail, colsum, overflow = ss.counts
        invariants.check_packed_counts(
            colsum, overflow, n_tokens=self.stream.n_tokens,
            where=f"epoch {ss.iteration} close (packed counts)")

    def _eval_parts(self, ss: StreamState) -> tuple:
        if self.paged:
            d_packed, colsum, _ov = ss.counts
            return (sparse.densify_rows(d_packed, self.layout.n_topics),
                    None, colsum)
        d_packed, w_head, w_tail, colsum, _ov = ss.counts
        return (sparse.densify_rows(d_packed, self.layout.n_topics),
                self.layout.densify_w(w_head, w_tail), colsum)

    # -- compiled pieces ----------------------------------------------------

    def _get_begin(self) -> Callable:
        if self._begin_fn is None:
            cfg, lay = self.config, self.layout
            k_total = lay.n_topics
            n = self.n_tokens
            paged = self.paged

            def begin(counts, key):
                if paged:
                    # only the document side densifies; W pages per
                    # shard from the host mirror
                    d_packed, colsum, _overflow = counts
                    key_next, sub = jax.random.split(key)
                    u = jax.random.uniform(sub, (n,), dtype=jnp.float32)
                    d_dense = sparse.densify_rows_sorted(d_packed,
                                                         k_total)
                    deltas = (jnp.zeros_like(d_dense),
                              jnp.zeros_like(colsum))
                    return key_next, u, (d_dense,), deltas
                d_packed, w_head, w_tail, colsum, _overflow = counts
                key_next, sub = jax.random.split(key)
                u = jax.random.uniform(sub, (n,), dtype=jnp.float32)
                d_dense = sparse.densify_rows_sorted(d_packed, k_total)
                w_parts = [w_head] + [
                    sparse.densify_rows_sorted(b, k_total) for b in w_tail]
                w_int = jnp.concatenate(w_parts, axis=0) \
                    if len(w_parts) > 1 else w_head
                W_hat = esca.compute_w_hat_from_colsum(w_int, colsum,
                                                       cfg.beta)
                stats_w = three_branch.word_stats(W_hat, g=cfg.g,
                                                  alpha=cfg.alpha_)
                deltas = (jnp.zeros_like(d_dense), jnp.zeros_like(w_int),
                          jnp.zeros_like(colsum))
                return key_next, u, (d_dense, w_int, W_hat, stats_w), \
                    deltas

            self._begin_fn = jax.jit(begin)
        return self._begin_fn

    def _apply_epoch(self, counts: tuple, derived: tuple,
                     deltas: tuple) -> tuple:
        if self.paged:
            if self._end_fn is None:
                lay = self.layout

                def end_paged(colsum, overflow, d_dense, deltas):
                    dD, dcs = deltas
                    d_packed, ov_d = sparse.pack_rows_sorted(
                        d_dense + dD, lay.d_capacity)
                    return d_packed, colsum + dcs, overflow + ov_d

                self._end_fn = jax.jit(end_paged, donate_argnums=(0,))
            _d_packed, colsum, overflow = counts
            (d_dense,) = derived
            return self._end_fn(colsum, overflow, d_dense, deltas)
        if self._end_fn is None:
            lay = self.layout

            def end(colsum, overflow, d_dense, w_int, deltas):
                dD, dW, dcs = deltas
                d_new = d_dense + dD
                w_new = w_int + dW
                colsum = colsum + dcs
                d_packed, ov_d = sparse.pack_rows_sorted(d_new,
                                                         lay.d_capacity)
                overflow = overflow + ov_d
                w_head = w_new[:lay.v_dense]
                new_tail = []
                for b in range(len(lay.tail_caps)):
                    start = lay.tail_starts[b]
                    end_ = lay.tail_starts[b + 1] \
                        if b + 1 < len(lay.tail_starts) else lay.n_words
                    bucket, ov_b = sparse.pack_rows_sorted(
                        w_new[start:end_], lay.tail_caps[b])
                    new_tail.append(bucket)
                    overflow = overflow + ov_b
                return (d_packed, w_head, tuple(new_tail), colsum,
                        overflow)

            # colsum is the only input whose buffer an output can alias
            # (the packed outputs have packed shapes); everything else is
            # freed when the epoch carry drops
            self._end_fn = jax.jit(end, donate_argnums=(0,))
        _d_packed, _w_head, _w_tail, colsum, overflow = counts
        d_dense, w_int, _W_hat, _stats = derived
        return self._end_fn(colsum, overflow, d_dense, w_int, deltas)

    def _get_shard_fn(self, capacity: int, win_words: int) -> Callable:
        sig = (capacity, win_words)
        fn = self._shard_cache.get(sig)
        if fn is not None:
            return fn
        cfg, lay = self.config, self.layout
        st = self.stream
        L, n = st.shard_len, self.n_tokens
        n_chunks = max(1, -(-L // capacity))
        track_span = self.balance == "tiles"
        split_tail = cfg.tail_sampler == "sparse" \
            and lay.v_dense < self.n_words
        if self.paged:
            P, V = self._page_rows, self.n_words

            def paged_fn(u, base, lo, topics_s, word_s, doc_s, mask_s,
                         w_win, counts, derived, deltas):
                d_packed = counts[0]
                colsum = counts[1]
                (d_dense,) = derived
                W_hat = esca.compute_w_hat_from_colsum(
                    w_win, colsum, cfg.beta, n_words=V)
                stats_w = three_branch.word_stats(W_hat, g=cfg.g,
                                                  alpha=cfg.alpha_)
                # window-LOCAL ids feed every Ŵ/stats gather; the
                # head/tail split keys on GLOBAL ids (the layout's
                # dense-word threshold lives in vocabulary space)
                word_l = jnp.clip(word_s - base, 0, P - 1).astype(jnp.int32)
                dec = three_branch.skip_phase(u, word_l, doc_s, d_dense,
                                              stats_w, g=cfg.g,
                                              alpha=cfg.alpha_)
                k1_per_word = stats_w.k[:, 0]
                dense_chunk = self._dense_chunk_sampler(
                    u, word_l, doc_s, d_dense, W_hat, k1_per_word,
                    win_words=V, n_stream=L)

                sparse_tail_chunk = self._sparse_tail_sampler(
                    u, word_l, doc_s, d_packed, d_dense, W_hat, stats_w,
                    win_words=V, n_stream=L)

                if split_tail:
                    head_mask = word_s < lay.v_dense
                    segments = [(head_mask, dense_chunk),
                                (~head_mask, sparse_tail_chunk)]
                else:
                    segments = [(None, dense_chunk)]
                new_topics = dec.k1
                in_m_acc = jnp.zeros(L, jnp.bool_)
                n_surv_total = jnp.int32(0)
                slots = jnp.int32(0)
                for seg_mask, chunk_fn in segments:
                    skip_seg = dec.skip if seg_mask is None \
                        else dec.skip | ~seg_mask
                    rank, n_surv = three_branch.survivor_rank(skip_seg)
                    surv_idx = three_branch.compact_survivor_indices(
                        rank, skip_seg, n_chunks * capacity)
                    new_topics, in_m_seg = three_branch.run_survivor_chunks(
                        surv_idx, n_surv, new_topics, capacity=capacity,
                        n_chunks=n_chunks, sample_chunk=chunk_fn)
                    in_m_acc = in_m_acc | in_m_seg
                    n_surv_total = n_surv_total + n_surv
                    slots = slots + three_branch.chunk_slots(
                        n_surv, capacity, L)
                dD, dw_win, dcs = scatter_changed_deltas(
                    topics_s, new_topics, doc_s, word_l, mask_s,
                    capacity=capacity, D=deltas[0],
                    W=jnp.zeros((P, cfg.n_topics), jnp.int32),
                    colsum=deltas[1])
                sums = _shard_stat_sums(lo, n, dec, in_m_acc, new_topics,
                                        topics_s, slots)
                return (new_topics, (dD, dcs), dw_win, n_surv_total,
                        jnp.int32(0), sums)

            fn = jax.jit(paged_fn, donate_argnums=(3, 7, 10))
            self._shard_cache[sig] = fn
            return fn

        def shard_fn(u, lo, topics_s, word_s, doc_s, mask_s, counts,
                     derived, deltas):
            d_packed = counts[0]
            d_dense, _w_int, W_hat, stats_w = derived
            dec = three_branch.skip_phase(u, word_s, doc_s, d_dense,
                                          stats_w, g=cfg.g,
                                          alpha=cfg.alpha_)
            k1_per_word = stats_w.k[:, 0]
            dense_chunk = self._dense_chunk_sampler(
                u, word_s, doc_s, d_dense, W_hat, k1_per_word,
                win_words=win_words, n_stream=L)
            sparse_tail_chunk = self._sparse_tail_sampler(
                u, word_s, doc_s, d_packed, d_dense, W_hat, stats_w,
                win_words=win_words, n_stream=L)

            if split_tail:
                head_mask = word_s < lay.v_dense
                segments = [(head_mask, dense_chunk),
                            (~head_mask, sparse_tail_chunk)]
            else:
                segments = [(None, dense_chunk)]
            new_topics = dec.k1
            in_m_acc = jnp.zeros(L, jnp.bool_)
            n_surv_total = jnp.int32(0)
            slots = jnp.int32(0)
            max_span = jnp.int32(0)
            for seg_mask, chunk_fn in segments:
                skip_seg = dec.skip if seg_mask is None \
                    else dec.skip | ~seg_mask
                rank, n_surv = three_branch.survivor_rank(skip_seg)
                surv_idx = three_branch.compact_survivor_indices(
                    rank, skip_seg, n_chunks * capacity)
                if track_span:
                    max_span = jnp.maximum(max_span, self._max_chunk_span(
                        surv_idx, n_chunks, capacity, word_ids=word_s,
                        n_stream=L))
                new_topics, in_m_seg = three_branch.run_survivor_chunks(
                    surv_idx, n_surv, new_topics, capacity=capacity,
                    n_chunks=n_chunks, sample_chunk=chunk_fn)
                in_m_acc = in_m_acc | in_m_seg
                n_surv_total = n_surv_total + n_surv
                slots = slots + three_branch.chunk_slots(
                    n_surv, capacity, L)
            deltas = scatter_changed_deltas(
                topics_s, new_topics, doc_s, word_s, mask_s,
                capacity=capacity, D=deltas[0], W=deltas[1],
                colsum=deltas[2])
            sums = _shard_stat_sums(lo, n, dec, in_m_acc, new_topics,
                                    topics_s, slots)
            return new_topics, deltas, n_surv_total, max_span, sums

        fn = jax.jit(shard_fn, donate_argnums=(2, 8))
        self._shard_cache[sig] = fn
        return fn
