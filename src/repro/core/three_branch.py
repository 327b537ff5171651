r"""EZLDA three-branch sampling (paper §III, Eq 6-10) — the core contribution.

The two-branch ESCA decomposition ``p ∝ D[d]∘Ŵ[v] + α∘Ŵ[v]`` is extended by
singling out each word's most popular topic K1 (value a1 = max_k Ŵ[v][k]):

    p ∝ D[d]∘Ŵ'[v]  +  α∘Ŵ'[v]  +  (D[d]+α)∘Ŵ[v]^m          (Eq 6)
        \_ S' ____/     \_ Q' __/     \_ M branch _________/

where Ŵ' zeroes the K1 entry and Ŵ^m keeps only it. The M branch has a single
entry ``M = a1·(b1+α)`` (Eq 8, b1 = D[d][K1]).

The skip test (paper Fig 4b step 3): before constructing the expensive S'
term, bound it from above with the g-term tail estimate (Eq 9-10)

    S_est = Σ_{2≤i≤g} a_i·b_i + a_{g+1}·(len(d) − Σ_{1≤i≤g} b_i)  ≥  S'

(a_i = i-th largest entry of Ŵ[v], b_i = D[d] at that entry's topic; we use
len(d) = Σ_k D[d][k], which on TPU is one row-sum instead of the paper's extra
pass). Drawing u ~ U[0,1]:

    u < M/(M+S_est+Q')  ⇒  u·(M+S'+Q') < M  ⇒  the exact sampler would land
    in the M branch anyway  ⇒  assign K1 and skip S' entirely.

The same u is reused for the exact branch when the test fails (paper §III-B),
so skipping never changes the sampled distribution — that is the theorem this
module's property tests pin down.

Implementation notes (TPU adaptation, DESIGN.md §2):
  * per-word quantities (top-(g+1) values/indices of Ŵ[v], Q', ΣŴ) are
    computed once per word as V-vectors and gathered per token — the paper's
    "once per word" amortization without warp cooperation;
  * K1/K2 are pair-packed into one int32 exactly as the paper stores them;
  * the exact (un-skipped) branch is O(K) per token here. The default
    sampler gathers survivors into fixed-size chunks when few enough
    survive, so the saved work is real, mirroring the paper's shrinking
    workload, and draws every token otherwise (``_sample_adaptive``);
    kernels/ carries the fused Pallas version.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.core import esca
from repro.core.sparse import pack_pairs

__all__ = [
    "WordStats", "word_stats", "SkipDecision", "skip_phase",
    "exact_three_branch", "exact_three_branch_tiled", "ThreeBranchStats",
    "chunk_slots", "sample", "derived_capacity",
    "build_plan", "plan_for", "Plan", "survivor_rank",
    "compact_survivor_indices", "map_token_tiles", "run_survivor_chunks",
    "sample_from_uniforms", "uniforms_at",
]


# ---------------------------------------------------------------------------
# per-word phase (amortized over the word's tokens, paper Fig 4b steps 1/3)
# ---------------------------------------------------------------------------

class WordStats(NamedTuple):
    """Per-word quantities shared by every token of the word."""
    a: jax.Array          # (V, g+1) top-(g+1) values of Ŵ[v], descending
    k: jax.Array          # (V, g)   topic ids of the top-g values (k[:,0]=K1)
    k12_packed: jax.Array # (V,) int32 — K1/K2 pair-packed (paper §III-C)
    q_prime: jax.Array    # (V,)  Q' = α·(ΣŴ[v] − a1)
    wsum: jax.Array       # (V,)  ΣŴ[v]


@functools.partial(jax.jit, static_argnames=("g", "alpha", "pair_barrier"))
def word_stats(W_hat: jax.Array, *, g: int, alpha: float,
               pair_barrier: bool = True) -> WordStats:
    # The barrier stops XLA:CPU from fusing the top-k sort into each
    # consumer (which re-runs the sort per use — measured 30× slower).
    # Identity on values, so results are bit-identical. Inside a
    # shard_map XLA:CPU aborts on one barrier over top_k's pair, so a
    # data shard (``pair_barrier=False``) puts one on each output; a TPU
    # drops either form, but they compile to different one-chip programs.
    n_top = min(g + 1, W_hat.shape[-1])
    if pair_barrier:
        vals, idxs = jax.lax.optimization_barrier(
            jax.lax.top_k(W_hat, n_top))                    # (V, n_top)
    else:
        vals, idxs = map(jax.lax.optimization_barrier,
                         jax.lax.top_k(W_hat, n_top))
    if n_top < g + 1:
        # K ≤ g: no mass lies past the K real topics, so the missing
        # a_i are 0 and Eq 10's tail term vanishes (S_est is then exact)
        vals = jnp.pad(vals, ((0, 0), (0, g + 1 - n_top)))
        idxs = jnp.pad(idxs, ((0, 0), (0, g + 1 - n_top)), mode="edge")
    wsum = jnp.sum(W_hat, axis=-1)                          # (V,)
    q_prime = alpha * (wsum - vals[:, 0])
    k = idxs[:, :g].astype(jnp.int32)
    k2 = k[:, 1] if g >= 2 else jnp.zeros_like(k[:, 0])
    return WordStats(a=vals, k=k,
                     k12_packed=pack_pairs(k[:, 0], k2),
                     q_prime=q_prime, wsum=wsum)


# ---------------------------------------------------------------------------
# phase 1: the skip test (cheap, all tokens)
# ---------------------------------------------------------------------------

class SkipDecision(NamedTuple):
    skip: jax.Array       # (N,) bool — u proven to land in the M branch
    m: jax.Array          # (N,) f32 — M = a1·(b1+α)  (Eq 8)
    s_est: jax.Array      # (N,) f32 — Eq 10 upper bound on S'
    k1: jax.Array         # (N,) int32 — the word's most popular topic


@functools.partial(jax.jit, static_argnames=("g", "alpha"))
def skip_phase(u: jax.Array, word_ids: jax.Array, doc_ids: jax.Array,
               D: jax.Array, stats: WordStats, *, g: int,
               alpha: float) -> SkipDecision:
    """Eq 8-10 + the skip test. O(g) gathers per token, no O(K) work.

    Every per-token quantity is its own (N,) vector: an (N, g) array
    would be padded to 128 lanes on a TPU, 64× its size at g = 2.
    """
    a = [stats.a[:, i][word_ids] for i in range(g + 1)]     # a_1..a_{g+1}
    ktop = [stats.k[:, i][word_ids] for i in range(g)]      # K_1..K_g
    q_prime = stats.q_prime[word_ids]                       # (N,)
    len_d = jnp.sum(D, axis=-1, dtype=jnp.float32)[doc_ids] # (N,)
    # b_i = D[d][K_i], i = 1..g (g gathers per token)
    b = [D[doc_ids, k].astype(jnp.float32) for k in ktop]
    m = a[0] * (b[0] + alpha)                               # Eq 8
    # Eq 10: exact head terms (i = 2..g) + tail bound with a_{g+1}.
    head = jnp.zeros_like(m)
    for i in range(1, g):
        head = head + a[i] * b[i]
    b_sum = b[0]
    for i in range(1, g):
        b_sum = b_sum + b[i]
    tail = a[g] * (len_d - b_sum)
    s_est = head + tail
    skip = u * (m + s_est + q_prime) < m
    return SkipDecision(skip=skip, m=m, s_est=s_est, k1=ktop[0])


# ---------------------------------------------------------------------------
# phase 2: exact three-branch sampling (only needed for un-skipped tokens)
# ---------------------------------------------------------------------------

def _exact_token(u, d_row, w_hat_row, k1, alpha):
    """Exact Eq 6 sampling for one token (vmapped over a tile).

    Uses the *combined* sweep (same transport as kernels/sample_fused.py):
    per-topic mass (D[k]+α)·Ŵ[k] for k≠K1 partitions S'+Q' exactly, so ONE
    cumsum + ONE searchsorted replaces the paper's two tree descents —
    identical distribution (S'+Q' = Σ_{k≠K1}(D+α)Ŵ, per-topic mass equal),
    ~2× cheaper per un-skipped token (EXPERIMENTS.md §Perf L5).

    Returns (topic, in_m) where in_m flags tokens that still landed in the M
    branch after the exact S' was known ("skipped final sampling", Fig 12b).
    """
    d_f = d_row.astype(jnp.float32)
    k_iota = jnp.arange(w_hat_row.shape[-1])
    mass = jnp.where(k_iota == k1, 0.0, (d_f + alpha) * w_hat_row)
    m = w_hat_row[k1] * (d_f[k1] + alpha)                   # M branch
    cum = jnp.cumsum(mass)
    x = u * (m + cum[-1])                                   # m+S'+Q'
    in_m = x < m
    k_c = jnp.minimum(jnp.searchsorted(cum, x - m, side="right"),
                      cum.shape[-1] - 1).astype(jnp.int32)
    topic = jnp.where(in_m, k1, k_c)
    return topic, in_m


@functools.partial(jax.jit, static_argnames=("alpha", "tile_size"))
def exact_three_branch(u: jax.Array, word_ids: jax.Array, doc_ids: jax.Array,
                       k1_per_word: jax.Array, D: jax.Array, W_hat: jax.Array,
                       *, alpha: float, tile_size: int = 8192):
    """Dense-reference exact branch over a token batch (tiled lax.map)."""
    n = word_ids.shape[0]

    def token_fn(args):
        u_t, v_t, d_t = args
        return _exact_token(u_t, D[d_t], W_hat[v_t], k1_per_word[v_t],
                            jnp.float32(alpha))

    return jax.lax.map(token_fn, (u, word_ids, doc_ids),
                       batch_size=min(tile_size, n) if n else None)


@functools.partial(jax.jit, static_argnames=("alpha", "tile_size"))
def exact_three_branch_tiled(u: jax.Array, local_word: jax.Array,
                             doc_ids: jax.Array, k1_win: jax.Array,
                             D: jax.Array, w_win: jax.Array, *,
                             alpha: float, tile_size: int = 8192):
    """Tile-scheduled exact branch: Ŵ rows from a per-tile word WINDOW.

    The tile-scheduled dispatch (``config.balance == "tiles"``,
    DESIGN.md SS9) hands every chunk one ``(win_words, K)`` slice of Ŵ
    (and of the per-word K1 vector) covering the chunk's word run;
    ``local_word`` indexes into it. Same per-token arithmetic as
    ``exact_three_branch`` on identical row values ⇒ bit-equal — the
    window only changes where the gather reads from.
    """
    n = local_word.shape[0]

    def token_fn(args):
        u_t, l_t, d_t = args
        return _exact_token(u_t, D[d_t], w_win[l_t], k1_win[l_t],
                            jnp.float32(alpha))

    return jax.lax.map(token_fn, (u, local_word, doc_ids),
                       batch_size=min(tile_size, n) if n else None)


# ---------------------------------------------------------------------------
# full sampler: phase 1 + (compacted) phase 2
# ---------------------------------------------------------------------------

class ThreeBranchStats(NamedTuple):
    frac_skipped: jax.Array       # skipped S' construction (phase-1 skip)
    frac_m_final: jax.Array       # landed in M branch (skipped final sampling)
    frac_unchanged: jax.Array
    frac_at_max: jax.Array
    # Q'-branch landings (paper Eq 6's α∘Ŵ' term). Defaults to 0.0 on paths
    # that use the combined S'+Q' sweep and cannot attribute the branch.
    frac_q_branch: jax.Array | float = 0.0
    # exact-draw slots phase 2 computed, over the tokens (capped at 1):
    # 1.0 where every token is drawn, ceil(survivors/capacity)·capacity/N
    # where survivors run in fixed-capacity chunks (``chunk_slots``).
    # Every sampler in the package computes it; the NaN default only
    # marks a stats tuple built elsewhere as "not counted".
    frac_phase2_slots: jax.Array | float = float("nan")
    # 1.0 where the stepwise sampler ran phase 2 over compacted survivor
    # chunks, 0.0 where it drew every token (``_sample_adaptive``)
    phase2_compacted: jax.Array | float = 0.0


def chunk_slots(n_surv, capacity: int, n_slots: int):
    """Exact-draw slots that ``run_survivor_chunks`` computes for
    ``n_surv`` survivors: whole chunks of ``capacity``, at most
    ``n_slots`` (int32, on the device)."""
    n_run = (jnp.asarray(n_surv, jnp.int32) + capacity - 1) // capacity
    return jnp.minimum(n_run * capacity, n_slots).astype(jnp.int32)


# Survivor chunks at full survivorship under a derived capacity.
TARGET_CHUNKS = 64
# The derived plan runs phase 2 over survivor chunks when their estimated
# slots are under this share of the tokens, and draws every token
# otherwise: the break-even of the two on a TPU v5e (PERF.md §6).
COMPACT_BELOW = 0.69
# Tokens whose skip test estimates the survivor share before the branch.
ESTIMATE_TOKENS = 1 << 16


@dataclasses.dataclass(frozen=True)
class Plan:
    """Static sampling plan (built once per corpus/config)."""
    g: int
    tile_size: int
    capacity: int | None          # survivor-chunk capacity; None = reference
    # draw over survivor chunks when their estimated slots are under this
    # share of the tokens, else every token; None always takes the chunks
    # (a pinned capacity)
    compact_below: float | None = None


def derived_capacity(n_tokens: int, tile_size: int) -> int:
    """Survivor-chunk capacity for ``n_tokens``: whole tiles, so every
    ``lax.map`` tile keeps the reference's shape, about ``TARGET_CHUNKS``
    chunks when every token survives, and at most ``n_tokens``."""
    cap = tile_size * -(-n_tokens // (TARGET_CHUNKS * tile_size))
    return max(1, min(cap, n_tokens))


def build_plan(corpus, config) -> Plan:
    """An explicit ``survivor_capacity`` pins the chunk capacity and always
    compacts; unset, the capacity is derived from the padded corpus and
    the sampler picks its phase 2 from each iteration's survivors."""
    tile = config.tile_size
    return plan_for(-(-corpus.n_tokens // tile) * tile, config)


def plan_for(n_tokens: int, config) -> Plan:
    """``build_plan`` for a sampler call over ``n_tokens`` token slots (the
    trainer's padded corpus, or one data shard's tokens)."""
    if config.survivor_capacity:
        return Plan(g=config.g, tile_size=config.tile_size,
                    capacity=int(config.survivor_capacity))
    return Plan(g=config.g, tile_size=config.tile_size,
                capacity=derived_capacity(n_tokens, config.tile_size),
                compact_below=COMPACT_BELOW)


def _key_uniforms(key, n):
    """The iteration's uniforms from its key: ``uniform(key, (n,))``."""
    return jax.random.uniform(key, (n,), dtype=jnp.float32)


def uniforms_at(key, positions):
    """``uniform(key, (N,))[positions]`` for any ``N`` past the largest
    position, without drawing the other values.

    JAX's partitionable threefry (its default) gives element ``i`` of a
    1-D draw the counter ``i``: the bits are ``threefry2x32(key, (0, i))``
    folded by xor, and the float is made from them as ``jax.random.uniform``
    makes it (the top 23 bits as the mantissa of a number in [1, 2),
    less 1). So a data shard draws its tokens' values of the single
    backend's one vector from their global positions.
    """
    if not jax.config.jax_threefry_partitionable:
        raise ValueError(
            "uniforms_at needs jax_threefry_partitionable (JAX's default): "
            "without it an element of a draw depends on the draw's length")
    from jax.extend.random import threefry2x32_p
    k = jax.random.key_data(key)
    lo = positions.astype(jnp.uint32)
    b1, b2 = threefry2x32_p.bind(k[0], k[1], jnp.zeros_like(lo), lo)
    mant = jax.lax.shift_right_logical(b1 ^ b2, jnp.uint32(32 - 23))
    one = jnp.uint32(0x3F800000)                     # 1.0f's bits
    return jax.lax.bitcast_convert_type(mant | one, jnp.float32) \
        - jnp.float32(1.0)


def _given_uniforms(u, n):
    """The iteration's uniforms, drawn by the caller (a data shard's
    tokens' values of the one ``uniform(key, (N,))``)."""
    return u


def _reference_body(src, word_ids, doc_ids, old_topics, D, W_hat, *, g,
                    alpha, tile_size, uniforms=_key_uniforms,
                    token_mean=jnp.mean, stats=word_stats):
    """``_sample_reference``'s work. ``uniforms(src, n)`` gives the
    tokens' uniforms where the draw has always been made, ``token_mean``
    reduces each per-token stat and ``stats`` makes the word stats, so the
    one-chip program keeps its exact form while a data shard passes its
    own uniforms, a masked reduction and shard_map-safe word stats."""
    stats_w = stats(W_hat, g=g, alpha=alpha)
    n = word_ids.shape[0]
    u = uniforms(src, n)
    dec = skip_phase(u, word_ids, doc_ids, D, stats_w, g=g, alpha=alpha)
    topics_exact, in_m = exact_three_branch(
        u, word_ids, doc_ids, stats_w.k[:, 0], D, W_hat,
        alpha=alpha, tile_size=tile_size)
    # Skip ⇒ K1; theorem guarantees topics_exact == K1 there (tested).
    new_topics = jnp.where(dec.skip, dec.k1, topics_exact)
    st = ThreeBranchStats(
        frac_skipped=token_mean(dec.skip.astype(jnp.float32)),
        frac_m_final=token_mean(in_m.astype(jnp.float32)),
        frac_unchanged=token_mean(
            (new_topics == old_topics).astype(jnp.float32)),
        frac_at_max=token_mean((new_topics == dec.k1).astype(jnp.float32)),
        frac_phase2_slots=jnp.float32(1.0),     # every token is drawn
        phase2_compacted=jnp.float32(0.0),
    )
    return new_topics, st


@functools.partial(jax.jit, static_argnames=("g", "alpha", "tile_size"))
def _sample_reference(key, word_ids, doc_ids, old_topics, D, W_hat,
                      *, g, alpha, tile_size):
    """Reference path: phase 1 for stats + exact phase 2 for *all* tokens.

    Identical output to the compacted path (same u per token); the
    oracle, and the adaptive sampler's dense branch as it stands: on a
    TPU v5e this program took 8.2 s at 25 M tokens where the same work
    arranged otherwise took 13 s (PERF.md §6). Its phases carry the
    nested programs' names in the op metadata (``jit(word_stats)``,
    ``jit(skip_phase)``, ``jit(exact_three_branch)``).
    """
    return _reference_body(key, word_ids, doc_ids, old_topics, D, W_hat,
                           g=g, alpha=alpha, tile_size=tile_size)


def compact_survivor_indices(rank, skip, total_slots):
    """Dense survivor token-index list, built with ONE O(N) scatter.

    Returns a (total_slots,) int32 buffer whose first n_surv entries are the
    token indices of the un-skipped tokens in rank order; the tail holds the
    out-of-range sentinel ``n``. Chunked consumers dynamic-slice O(capacity)
    windows out of it and scatter results back with ``mode="drop"`` — the
    sentinel slots drop, and no valid-mask read-modify-write is needed
    (that pattern puts duplicate indices in one scatter, an XLA-order
    hazard). Gathers at the sentinel clamp to token n−1; results dropped.
    """
    n = rank.shape[0]
    slot = jnp.where(skip, total_slots, rank)               # pads → dumped
    buf = jnp.full((total_slots + 1,), n, jnp.int32)
    buf = buf.at[slot].set(jnp.arange(n, dtype=jnp.int32), mode="drop")
    return buf[:total_slots]


def survivor_rank(skip: jax.Array):
    """(rank, n_surv): dense rank of each un-skipped token, survivor count."""
    rank = jnp.cumsum(~skip) - 1
    n_surv = (rank[-1] + 1).astype(jnp.int32) if skip.shape[0] \
        else jnp.int32(0)
    return rank, n_surv


def map_token_tiles(fn, idx, tile: int):
    """``fn`` over consecutive ``tile``-slot slices of token indices
    (``lax.map``), concatenated back to ``idx``'s length.

    A chunk may hold every token of a corpus (survivor capacity starts at
    N, and a data shard holds N / shards), so the (tokens, K) row gathers
    a draw needs must not be made for all of it at once: this keeps one
    tile's rows live. The tail tile is padded with index 0 and its
    results are dropped. Draws are per token, so the tiling never changes
    a value.
    """
    n = idx.shape[0]
    tile = min(tile, n)
    pad = (-n) % tile
    out = jax.lax.map(fn, jnp.pad(idx, (0, pad)).reshape(-1, tile))
    return jax.tree.map(lambda o: o.reshape(-1)[:n], out)


def run_survivor_chunks(surv_idx, n_surv, init_topics, *, capacity,
                        n_chunks, sample_chunk):
    """Cond-guarded fori_loop over fixed-capacity survivor chunks.

    The shared sync-free chunking pattern (also the fused pipeline's,
    train/lda_step.py): budget of ``n_chunks`` covers every token so
    correctness never depends on the survivor count; chunks past the
    survivor tail cost one predicate. ``sample_chunk(idx) -> (topics,
    in_m)`` supplies the phase-2 sampler (dense reference or Pallas
    kernel); results scatter back with ``mode="drop"`` so sentinel slots
    vanish. Returns (new_topics, in_m_acc).
    """
    n = init_topics.shape[0]

    def chunk_body(c, carry):
        def run_chunk(carry):
            new_topics, in_m_acc = carry
            idx = jax.lax.dynamic_slice(surv_idx, (c * capacity,),
                                        (capacity,))
            topics_c, in_m_c = sample_chunk(idx)
            new_topics = new_topics.at[idx].set(topics_c, mode="drop")
            in_m_acc = in_m_acc.at[idx].set(in_m_c, mode="drop")
            return new_topics, in_m_acc
        return jax.lax.cond(c * capacity < n_surv, run_chunk,
                            lambda carry: carry, carry)

    return jax.lax.fori_loop(0, n_chunks, chunk_body,
                             (init_topics, jnp.zeros(n, jnp.bool_)))


def _compacted_body(src, word_ids, doc_ids, old_topics, D, W_hat, *, g,
                    alpha, capacity, tile_size, uniforms=_key_uniforms,
                    token_mean=jnp.mean, stats=word_stats):
    """``_sample_compacted``'s work; the hooks as in ``_reference_body``."""
    stats_w = stats(W_hat, g=g, alpha=alpha)
    n = word_ids.shape[0]
    u = uniforms(src, n)
    dec = skip_phase(u, word_ids, doc_ids, D, stats_w, g=g, alpha=alpha)
    rank, n_surv = survivor_rank(dec.skip)
    k1_per_word = stats_w.k[:, 0]
    n_chunks = max(1, -(-n // capacity))
    surv_idx = compact_survivor_indices(rank, dec.skip, n_chunks * capacity)

    def sample_chunk(idx):
        return exact_three_branch(
            u[idx], word_ids[idx], doc_ids[idx], k1_per_word, D, W_hat,
            alpha=alpha, tile_size=tile_size)

    new_topics, in_m_acc = run_survivor_chunks(
        surv_idx, n_surv, dec.k1,                           # skipped ⇒ K1
        capacity=capacity, n_chunks=n_chunks, sample_chunk=sample_chunk)
    st = ThreeBranchStats(
        frac_skipped=token_mean(dec.skip.astype(jnp.float32)),
        frac_m_final=token_mean((dec.skip | in_m_acc).astype(jnp.float32)),
        frac_unchanged=token_mean(
            (new_topics == old_topics).astype(jnp.float32)),
        frac_at_max=token_mean((new_topics == dec.k1).astype(jnp.float32)),
        frac_phase2_slots=chunk_slots(n_surv, capacity, n).astype(
            jnp.float32) / max(n, 1),
        phase2_compacted=jnp.float32(1.0),
    )
    return new_topics, st


@functools.partial(jax.jit,
                   static_argnames=("g", "alpha", "capacity", "tile_size"))
def _sample_compacted(key, word_ids, doc_ids, old_topics, D, W_hat,
                      *, g, alpha, capacity, tile_size):
    """Compacted path as ONE dispatch: fori_loop over a static chunk budget.

    The chunk budget is ceil(N/capacity) — full coverage, so correctness
    never depends on how many tokens actually survive — but each chunk body
    is guarded by ``lax.cond(lo < n_surv, ...)``: chunks past the survivor
    tail cost one predicate, not one kernel. The survivor count therefore
    never leaves the device (the seed's ``int(n_surv)`` sync is gone) and
    runtime phase-2 work stays proportional to ceil(survivors/capacity).
    """
    return _compacted_body(key, word_ids, doc_ids, old_topics, D, W_hat,
                           g=g, alpha=alpha, capacity=capacity,
                           tile_size=tile_size)


def _adaptive_body(src, word_ids, doc_ids, old_topics, D, W_hat, *, g,
                   alpha, capacity, tile_size, compact_below, uniforms,
                   compacted, reference, stats=word_stats):
    """``_sample_adaptive``'s work: the estimate, then ``lax.cond``
    between ``compacted`` and ``reference``, each given ``src``."""
    n = word_ids.shape[0]
    stride = max(1, n // ESTIMATE_TOKENS)
    stats_w = stats(W_hat, g=g, alpha=alpha)
    u = uniforms(src, n)
    dec = skip_phase(u[::stride], word_ids[::stride], doc_ids[::stride], D,
                     stats_w, g=g, alpha=alpha)
    est_surv = jnp.round(jnp.mean((~dec.skip).astype(jnp.float32)) * n)
    est_slots = chunk_slots(est_surv.astype(jnp.int32), capacity, n)
    compact = est_slots.astype(jnp.float32) < jnp.float32(compact_below * n)
    args = (src, word_ids, doc_ids, old_topics, D, W_hat)
    return jax.lax.cond(compact, lambda a: compacted(*a),
                        lambda a: reference(*a), args)


@functools.partial(jax.jit, static_argnames=(
    "g", "alpha", "capacity", "tile_size", "compact_below"))
def _sample_adaptive(key, word_ids, doc_ids, old_topics, D, W_hat,
                     *, g, alpha, capacity, tile_size, compact_below):
    """The sampler as ONE sync-free dispatch that picks its phase 2 on the
    device from this iteration's survivors.

    The skip test over every ``n // ESTIMATE_TOKENS``-th token, with the
    iteration's own uniforms, estimates the share of chunk slots the
    survivors fill; ``lax.cond`` then runs ``_sample_compacted`` when that
    is under ``compact_below`` and ``_sample_reference`` otherwise.
    Ranking, scattering and gathering near-full survivor sets costs more
    than the draws it saves. Each branch is its program as it stands, word
    stats and skip test included: the reference's schedule is what makes
    it fast on a TPU v5e, and the estimate costs a small fraction of
    either. Both draw each token from the same ``u`` with the same
    arithmetic, so the topics equal ``_sample_reference``'s bit for bit,
    whichever branch runs.
    """
    return _adaptive_body(
        key, word_ids, doc_ids, old_topics, D, W_hat, g=g, alpha=alpha,
        capacity=capacity, tile_size=tile_size, compact_below=compact_below,
        uniforms=_key_uniforms,
        compacted=functools.partial(_sample_compacted, g=g, alpha=alpha,
                                    capacity=capacity, tile_size=tile_size),
        reference=functools.partial(_sample_reference, g=g, alpha=alpha,
                                    tile_size=tile_size))


def sample(key, plan: Plan, word_ids, doc_ids, old_topics, D, W, config):
    """Full EZLDA sampler: Ŵ, phase 1, (compacted) phase 2, stats.

    The derived plan (``build_plan``) runs ``_sample_adaptive``: exact
    sampling over ceil(survivors/capacity) chunks whenever that is the
    cheaper phase 2, over every token otherwise, in one sync-free
    dispatch. A pinned capacity always runs the chunks
    (``_sample_compacted``; train/lda_step.py builds its fused scanned
    iteration on the same machinery); ``capacity=None`` is the reference,
    which draws every token.
    """
    alpha, beta = config.alpha_, config.beta
    W_hat = esca.compute_w_hat(W, beta)
    if plan.capacity is None:
        return _sample_reference(
            key, word_ids, doc_ids, old_topics, D, W_hat, g=plan.g,
            alpha=alpha, tile_size=plan.tile_size)
    if plan.compact_below is None:
        return _sample_compacted(
            key, word_ids, doc_ids, old_topics, D, W_hat, g=plan.g,
            alpha=alpha, capacity=plan.capacity, tile_size=plan.tile_size)
    return _sample_adaptive(key, word_ids, doc_ids, old_topics, D, W_hat,
                            g=plan.g, alpha=alpha, capacity=plan.capacity,
                            tile_size=plan.tile_size,
                            compact_below=plan.compact_below)


def sample_from_uniforms(u, plan: Plan, word_ids, doc_ids, old_topics, D,
                         W_hat, *, alpha: float, token_mean=jnp.mean):
    """``sample``'s draw with the tokens' uniforms ``u`` given, for a data
    shard that holds some of the corpus's tokens: with their values of the
    one ``uniform(key, (N,))``, the same D rows and the same Ŵ, each token
    draws the topic the single backend draws. Traced inline (call it
    inside the caller's program); ``token_mean`` reduces each per-token
    stat, so a shard can leave its pad slots out and sum over the mesh
    after the branch (no collective may run inside a branch that shards
    choose apart)."""
    stats = functools.partial(word_stats, pair_barrier=False)
    kw = dict(g=plan.g, alpha=alpha, tile_size=plan.tile_size,
              uniforms=_given_uniforms, token_mean=token_mean, stats=stats)
    args = (u, word_ids, doc_ids, old_topics, D, W_hat)
    reference = functools.partial(_reference_body, **kw)
    if plan.capacity is None:
        return reference(*args)
    compacted = functools.partial(_compacted_body, capacity=plan.capacity,
                                  **kw)
    if plan.compact_below is None:
        return compacted(*args)
    return _adaptive_body(*args, g=plan.g, alpha=alpha,
                          capacity=plan.capacity, tile_size=plan.tile_size,
                          compact_below=plan.compact_below,
                          uniforms=_given_uniforms, compacted=compacted,
                          reference=reference, stats=stats)
