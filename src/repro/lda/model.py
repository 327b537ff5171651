"""LDA configuration and training state (dense and hybrid-sparse layouts)."""

from __future__ import annotations

import dataclasses
from typing import Any, NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import sparse

__all__ = ["DistConfig", "LDAConfig", "LDAState", "SparseLDAState",
           "HybridLayout", "head_rows_for_coverage"]


def head_rows_for_coverage(row_mass, coverage: float = 0.9) -> int:
    """Smallest H such that rows [0, H) hold >= ``coverage`` of the mass.

    Under the engine's frequency relabeling, row mass (a word's token
    count — ``W.sum(axis=1)`` gives exactly this) is non-increasing in
    the row id, so the head prefix is the heaviest hot set of its size.
    The serving tier uses this to size its pinned hot-word cache
    (``repro.serve.cache``) to a target hit rate on traffic that matches
    the training distribution. Always returns at least 1; a
    non-positive total mass returns 1 (nothing to cover).
    """
    if not 0.0 < coverage <= 1.0:
        raise ValueError(f"coverage={coverage} must be in (0, 1]")
    m = np.asarray(row_mass, np.float64).ravel()
    total = float(m.sum())
    if m.size == 0 or total <= 0.0:
        return 1
    cum = np.cumsum(m)
    return int(np.searchsorted(cum, coverage * total, side="left")) + 1


@dataclasses.dataclass(frozen=True)
class DistConfig:
    """Grouped distributed-training knobs (``LDAConfig.dist``).

    One field instead of loose top-level knobs scattered over LDAConfig:
    everything that only matters when training spans more than one
    device lives here, and ``__post_init__`` is its one validation
    point (the same discipline LDAConfig follows for the single-host
    knobs). The legacy top-level ``balance`` knob keeps working for one
    release through a mapping shim in ``LDAConfig.__post_init__`` that
    warns once per process.

    ``w_sync`` picks how the word-topic matrix W is kept in sync across
    data shards:

      * ``"replicate"`` — the paper's §V-B scheme: every shard holds a
        full W replica, rebuilt each iteration by one delta all-reduce
        (``psum``). Model size is capped by one host's memory.
      * ``"ps"`` — word-sharded parameter server (DESIGN.md SS15): each
        owner holds one contiguous word-range of W; workers pull the
        page of rows their current token sub-shard touches, push int32
        delta blocks back, and a stale-synchronous clock bounds how far
        any worker may run ahead. ``staleness=0`` is bitwise-equal to
        the replicated path.
    """

    mesh_shape: tuple = ()        # (("data", 4), ("model", 2)); () = engine
                                  # default (all devices on the data axis)
    balance: str = "none"         # "none" | "tiles" (paper §V-A at shard
                                  # granularity)
    w_sync: str = "replicate"     # "replicate" | "ps"
    staleness: int = 0            # SSP bound: how many rounds a worker may
                                  # run ahead of the slowest (w_sync="ps")
    owner_layout: str = "rows"    # owner word-ranges: "rows" (equal row
                                  # counts) | "mass" (equal token mass)
    n_owners: int | None = None   # None = one owner per data shard

    def __post_init__(self) -> None:
        if self.w_sync not in ("replicate", "ps"):
            raise ValueError(
                f"unknown w_sync {self.w_sync!r}: expected 'replicate' "
                "(the paper's §V-B full-replica delta all-reduce) or 'ps' "
                "(word-sharded parameter server, DESIGN.md SS15)")
        if self.balance not in ("none", "tiles"):
            raise ValueError(
                f"unknown balance {self.balance!r}: valid options are "
                "'none' or 'tiles' (hierarchical tile-scheduled workload "
                "balancing, paper SSV-A / DESIGN.md SS9)")
        if self.staleness < 0:
            raise ValueError(
                f"staleness={self.staleness} must be >= 0: it bounds how "
                "many commit rounds a worker may run ahead (0 = bulk-"
                "synchronous, bitwise-equal to w_sync='replicate')")
        if self.staleness > 0 and self.w_sync != "ps":
            raise ValueError(
                f"staleness={self.staleness} needs w_sync='ps': the "
                "replicated path is bulk-synchronous by construction "
                "(every iteration ends in one all-reduce)")
        if self.owner_layout not in ("rows", "mass"):
            raise ValueError(
                f"unknown owner_layout {self.owner_layout!r}: expected "
                "'rows' (equal word-row counts per owner) or 'mass' "
                "(equal token mass per owner)")
        if self.n_owners is not None and self.n_owners < 1:
            raise ValueError(
                f"n_owners={self.n_owners} must be >= 1 (or None for one "
                "owner per data shard)")
        if self.w_sync != "ps" and self.n_owners is not None:
            raise ValueError(
                f"n_owners={self.n_owners} is only consumed by "
                "w_sync='ps' (owner word-ranges exist only on the "
                "parameter-server path)")
        if self.mesh_shape:
            for entry in self.mesh_shape:
                if (not isinstance(entry, tuple) or len(entry) != 2
                        or not isinstance(entry[0], str)
                        or int(entry[1]) < 1):
                    raise ValueError(
                        f"mesh_shape entry {entry!r} must be an "
                        "(axis_name, extent>=1) pair, e.g. "
                        "(('data', 4), ('model', 1))")
            names = [a for a, _ in self.mesh_shape]
            if "model" not in names:
                raise ValueError(
                    f"mesh_shape axes {names} lack a 'model' axis: the "
                    "distributed trainer needs one (size 1 reproduces "
                    "the paper's pure data-parallel scheme)")


_LOOSE_DIST_KNOB_WARNED = False


@dataclasses.dataclass(frozen=True)
class LDAConfig:
    n_topics: int
    alpha: float | None = None       # paper: 50/K when None
    beta: float = 0.01               # paper SS II-B
    sampler: str = "three_branch"    # "two_branch" | "three_branch" | "warp"
    impl: str = "xla"                # "xla" | "pallas"
    g: int = 2                       # Eq 10 tail-bound terms (paper uses 2)
    mh_cycles: int = 2               # warp: MH proposal cycles per token
    tile_size: int = 8192            # token tile (balance.py); pow2
    format: str = "dense"            # live-state layout: "dense" | "hybrid"
    tail_sampler: str = "exact"      # hybrid tail phase-2: "exact" | "sparse"
    balance: str = "none"            # workload balancing: "none" | "tiles"
    d_capacity: int | None = None    # packed-ELL D row capacity; None=auto
    survivor_capacity: int | None = None  # phase-2 chunk size pin; None=derived
    dense_word_threshold: int | None = None  # tokens>=thr => dense W row; None=K (paper)
    fused: bool = False              # route run() through train/lda_step.py
    corpus_residency: str = "full"   # T: "full" | "streamed" | "auto" | "disk"
    corpus_path: str | None = None   # CorpusStore directory (residency "disk")
    stream_shards: int | None = None  # epoch shards when streamed; None=auto
    device_budget_bytes: int | None = None  # residency budget; None=device-derived
    selfcheck: bool = False          # count-invariant tripwires (invariants.py)
    stream_watchdog_seconds: float | None = None  # prefetch deadline; None=off
    seed: int = 0
    eval_every: int = 10
    dist: DistConfig | None = None   # grouped distributed knobs; None =
                                     # synthesized from the loose top-level
                                     # knobs (deprecated, warns once)

    def __post_init__(self) -> None:
        # The ONE validation point for every knob (DESIGN.md SS7): trainers,
        # pipelines, and the engine all consume an already-validated config,
        # so a bad knob fails here — at construction, with the full menu —
        # never deep inside a backend __init__ or a traced function.
        # -- grouped-dist shim: `dist` is authoritative; the loose top-level
        # `balance` knob maps into it for one release (warns once), and the
        # top-level field is kept in sync so existing readers stay correct.
        if self.dist is None:
            if self.balance != "none":
                global _LOOSE_DIST_KNOB_WARNED
                if not _LOOSE_DIST_KNOB_WARNED:
                    _LOOSE_DIST_KNOB_WARNED = True
                    import warnings
                    warnings.warn(
                        "the top-level LDAConfig.balance knob is moving "
                        "into the grouped LDAConfig.dist field: pass "
                        "dist=DistConfig(balance=...) instead (the loose "
                        "knob keeps working for one release)",
                        DeprecationWarning, stacklevel=3)
            object.__setattr__(self, "dist",
                               DistConfig(balance=self.balance))
        else:
            if not isinstance(self.dist, DistConfig):
                raise ValueError(
                    f"dist={self.dist!r} must be a DistConfig (or None "
                    "to synthesize one from the loose top-level knobs)")
            if self.balance != "none" and self.balance != self.dist.balance:
                raise ValueError(
                    f"balance={self.balance!r} conflicts with "
                    f"dist.balance={self.dist.balance!r}: set it in "
                    "DistConfig only (the top-level knob is a deprecated "
                    "alias)")
            object.__setattr__(self, "balance", self.dist.balance)
        if self.n_topics < 1:
            raise ValueError(f"n_topics={self.n_topics} must be >= 1")
        if self.sampler not in ("two_branch", "three_branch", "warp"):
            raise ValueError(
                f"unknown sampler {self.sampler!r}: valid options are "
                "'two_branch' (ESCA baseline), 'three_branch' (exact EZLDA "
                "skip sampler), or 'warp' (WarpLDA-style Metropolis-"
                "Hastings, DESIGN.md SS12)")
        if self.impl not in ("xla", "pallas"):
            raise ValueError(
                f"unknown impl {self.impl!r}: valid options are 'xla' "
                "(pure-XLA reference) or 'pallas' (tiled kernels)")
        if self.format not in ("dense", "hybrid"):
            raise ValueError(f"unknown state format {self.format!r}: "
                             "expected 'dense' or 'hybrid'")
        if self.tail_sampler not in ("exact", "sparse"):
            raise ValueError(f"unknown tail_sampler {self.tail_sampler!r}: "
                             "expected 'exact' or 'sparse'")
        if self.balance not in ("none", "tiles"):
            raise ValueError(
                f"unknown balance {self.balance!r}: valid options are "
                "'none' or 'tiles' (hierarchical tile-scheduled workload "
                "balancing, paper SSV-A / DESIGN.md SS9)")
        if self.g < 1:
            raise ValueError(f"g={self.g} must be >= 1 (paper uses 2)")
        if self.mh_cycles < 1:
            raise ValueError(
                f"mh_cycles={self.mh_cycles} must be >= 1: each cycle of "
                "the warp sampler issues one doc and one word proposal, "
                "and an MH chain with zero proposals never moves")
        if self.tile_size < 1:
            raise ValueError(f"tile_size={self.tile_size} must be >= 1")
        if self.eval_every < 1:
            raise ValueError(f"eval_every={self.eval_every} must be >= 1")
        if self.alpha is not None and self.alpha <= 0:
            raise ValueError(f"alpha={self.alpha} must be positive "
                             "(or None for the paper's 50/K)")
        if self.beta <= 0:
            raise ValueError(f"beta={self.beta} must be positive")
        for knob in ("d_capacity", "survivor_capacity",
                     "dense_word_threshold", "device_budget_bytes"):
            v = getattr(self, knob)
            if v is not None and v < 1:
                raise ValueError(f"{knob}={v} must be >= 1 (or None for auto)")
        if self.corpus_residency not in ("full", "streamed", "auto",
                                         "disk"):
            raise ValueError(
                f"unknown corpus_residency {self.corpus_residency!r}: "
                "expected 'full' (token list device-resident), 'streamed' "
                "(epoch-sharded out-of-core pipeline, DESIGN.md SS10), "
                "'auto' (streamed iff estimated token bytes exceed the "
                "device budget), or 'disk' (disk-native CorpusStore with "
                "paged W, DESIGN.md SS14)")
        if self.corpus_residency == "disk" and self.corpus_path is None:
            raise ValueError(
                "corpus_residency='disk' needs corpus_path: point it at a "
                "CorpusStore directory (write one with "
                "ShardedCorpus.to_store(path))")
        if self.corpus_path is not None \
                and self.corpus_residency not in ("disk", "auto"):
            raise ValueError(
                f"corpus_path={self.corpus_path!r} is only consumed by "
                "corpus_residency='disk' (or 'auto', which resolves to "
                "'disk' when a path is set — docs/API.md residency "
                f"table), got {self.corpus_residency!r}: set both or "
                "neither, so a config never silently trains from a "
                "different corpus than the one named")
        if self.stream_shards is not None and self.stream_shards < 2:
            raise ValueError(
                f"stream_shards={self.stream_shards} must be >= 2 (or None "
                "for the budget-derived count): streaming needs at least "
                "a resident shard and a prefetched shard")
        if self.corpus_path is not None and self.stream_shards is not None:
            raise ValueError(
                f"stream_shards={self.stream_shards} conflicts with "
                "disk-native residency (corpus_path set): the shard grid "
                "is fixed by the CorpusStore manifest — leave "
                "stream_shards None (re-shard by rewriting the store)")
        if self.stream_watchdog_seconds is not None \
                and self.stream_watchdog_seconds <= 0:
            raise ValueError(
                f"stream_watchdog_seconds={self.stream_watchdog_seconds} "
                "must be > 0 (or None to wait on prefetch indefinitely)")

    @property
    def alpha_(self) -> float:
        return 50.0 / self.n_topics if self.alpha is None else self.alpha

    @property
    def dense_threshold_(self) -> int:
        # Paper heuristic (SS IV-B): a word with >= K tokens may touch every
        # topic, so sparse storage cannot beat dense for it.
        return self.n_topics if self.dense_word_threshold is None else \
            self.dense_word_threshold


class LDAState(NamedTuple):
    """Device-resident training state, dense layout.

    D and W are *derived* from (corpus, topics); checkpoints persist only
    topics + rng + iteration, which makes restore elastic (DESIGN.md SS6).
    """
    topics: jax.Array      # (N,) int32
    D: jax.Array           # (M, K) int32
    W: jax.Array           # (V, K) int32
    key: jax.Array         # PRNG key
    iteration: jax.Array   # () int32

    def host_payload(self) -> dict[str, Any]:
        return {
            "topics": np.asarray(self.topics),
            "key": np.asarray(jax.random.key_data(self.key)),
            "iteration": int(self.iteration),
        }

    def nbytes(self) -> int:
        """Measured live count-state bytes (D + W buffers)."""
        return int(self.D.size + self.W.size) * 4


class SparseLDAState(NamedTuple):
    """Device-resident training state, hybrid sparse layout (DESIGN.md SS5).

    D rows are packed ELL (topic<<16 | count per slot, SS IV-B pair
    packing); W splits into a dense head (frequent words) and a bucketed
    packed tail (HybridW made live). The Ŵ column sum rides along so Ŵ's
    denominator never needs the densified W. ``overflow`` counts ±1 updates
    the packed formats could not place — 0 by construction when capacities
    respect the row-nnz upper bounds (the overflow policy's tripwire).

    Checkpoint payloads stay topics + rng + iteration: both layouts restore
    from the same payload because the counts are derived state.
    """
    topics: jax.Array                 # (N,) int32
    D: jax.Array                      # (M, L_d) int32 packed ELL
    W_head: jax.Array                 # (V_dense, K) int32 dense head
    W_tail: tuple[jax.Array, ...]     # packed ELL buckets, decaying capacity
    colsum: jax.Array                 # (K,) int32 == Σ_v W[v][k]
    overflow: jax.Array               # () int32 dropped-update tripwire
    key: jax.Array                    # PRNG key
    iteration: jax.Array              # () int32

    def host_payload(self) -> dict[str, Any]:
        return {
            "topics": np.asarray(self.topics),
            "key": np.asarray(jax.random.key_data(self.key)),
            "iteration": int(self.iteration),
        }

    def nbytes(self) -> int:
        """Measured live count-state bytes (packed D + hybrid W + colsum)."""
        total = int(self.D.size + self.W_head.size + self.colsum.size)
        total += sum(int(b.size) for b in self.W_tail)
        return total * 4


@dataclasses.dataclass(frozen=True)
class HybridLayout:
    """Static shape plan for the hybrid live state (built once per corpus).

    Capacities are row-nnz UPPER BOUNDS, which is the overflow policy
    (DESIGN.md SS5): a D row holds at most min(doc_len, K) distinct topics
    and a tail W row at most min(token_count, K), so sizing slots at those
    bounds makes overflow impossible; a pinned ``d_capacity`` below the
    bound is rejected here (fail at build, never corrupt at runtime).
    """
    n_topics: int
    n_docs: int
    n_words: int
    d_capacity: int                   # uniform packed-ELL D row slots
    v_dense: int                      # words [0, v_dense) keep dense W rows
    tail_starts: tuple[int, ...]      # absolute word-id start per bucket
    tail_caps: tuple[int, ...]        # slots per row, halving per bucket

    @classmethod
    def build(cls, corpus, config: LDAConfig) -> "HybridLayout":
        counts = np.asarray(corpus.word_token_counts)
        if counts.size and not np.all(np.diff(counts) <= 0):
            raise ValueError(
                "format='hybrid' requires a frequency-relabeled corpus "
                "(word token counts non-increasing): call "
                "corpus.relabel_by_frequency before building the trainer")
        k = config.n_topics
        d_bound = int(min(max(int(corpus.doc_lengths.max(initial=1)), 1), k))
        if config.d_capacity is None:
            d_cap = d_bound
        else:
            d_cap = int(config.d_capacity)
            if d_cap < d_bound:
                raise ValueError(
                    f"d_capacity={d_cap} is below the D row-nnz upper bound "
                    f"min(max_doc_len, K)={d_bound}; such rows would "
                    "overflow their ELL slots and break bit-exactness. "
                    "Raise d_capacity (or leave it None for the auto bound)")
            d_cap = min(d_cap, k)
        thr = max(int(config.dense_threshold_), 1)
        v_dense = int(np.searchsorted(-counts, -thr, side="right"))
        tail_upper = np.minimum(counts[v_dense:], k)
        starts: list[int] = []
        caps: list[int] = []
        if len(tail_upper):
            plans = sparse.bucket_plan(tail_upper,
                                       max_capacity=int(min(thr, k)))
            for (s, _e, cap) in plans:
                starts.append(v_dense + s)
                caps.append(int(min(cap, k)))
        return cls(n_topics=k, n_docs=corpus.n_docs, n_words=corpus.n_words,
                   d_capacity=d_cap, v_dense=v_dense,
                   tail_starts=tuple(starts), tail_caps=tuple(caps))

    # -- conversions (dense <-> hybrid) ------------------------------------

    def pack_d(self, D: jax.Array) -> jax.Array:
        """(M, K) -> (M, L) packed, sorted-slot invariant (scatter-free)."""
        packed, _ = sparse.pack_rows_sorted(D, self.d_capacity)
        return packed

    def split_w(self, W: jax.Array):
        """Dense (V, K) W -> (dense head, packed tail buckets, sorted)."""
        head = W[:self.v_dense]
        tail = []
        for b, start in enumerate(self.tail_starts):
            end = self.tail_starts[b + 1] if b + 1 < len(self.tail_starts) \
                else self.n_words
            packed, _ = sparse.pack_rows_sorted(W[start:end],
                                                self.tail_caps[b])
            tail.append(packed)
        return head, tuple(tail)

    def densify_w(self, w_head: jax.Array,
                  w_tail: tuple[jax.Array, ...]) -> jax.Array:
        """(head, tail buckets) -> dense (V, K) int32 — exact (integers)."""
        parts = [w_head]
        for b in w_tail:
            parts.append(sparse.densify_rows(b, self.n_topics))
        return jnp.concatenate(parts, axis=0) if len(parts) > 1 else w_head

    def to_sparse(self, state: LDAState) -> SparseLDAState:
        w_head, w_tail = self.split_w(state.W)
        colsum = jnp.sum(state.W, axis=0, dtype=jnp.int32)
        key = jax.random.wrap_key_data(jnp.copy(
            jax.random.key_data(state.key)))
        return SparseLDAState(
            topics=jnp.copy(state.topics), D=self.pack_d(state.D),
            W_head=w_head, W_tail=w_tail, colsum=colsum,
            overflow=jnp.int32(0), key=key,
            iteration=jnp.copy(state.iteration))

    def to_dense(self, state: SparseLDAState) -> LDAState:
        return LDAState(
            topics=state.topics,
            D=sparse.densify_rows(state.D, self.n_topics),
            W=self.densify_w(state.W_head, state.W_tail),
            key=state.key, iteration=state.iteration)
