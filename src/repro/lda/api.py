"""One front door for LDA: the ``LDAEngine`` facade + the serving artifact.

The paper's pipeline (three-branch sampling, hybrid D/W live state,
multi-device scaling) used to hide behind three disjoint entry points —
``LDATrainer``, ``DistLDATrainer``, and a launcher that advertised an
``--lda`` mode it never wired — and had no inference path for unseen
documents at all. This module is the single public surface (DESIGN.md SS7):

``LDAEngine``
    Owns corpus prep (frequency relabeling when the layout needs it),
    backend selection (``backend="auto"|"single"|"distributed"``, auto by
    device count / mesh), and a scikit-style lifecycle — ``fit(n_iters)``,
    ``resume()``, ``score()`` — over ONE config (validated once, in
    ``LDAConfig.__post_init__``) and ONE checkpoint format. The trainers
    are internal backends; constructing them directly still works but is
    deprecated. Every config knob flows through unchanged — notably
    ``balance="tiles"`` (hierarchical tile-scheduled workload balancing,
    DESIGN.md SS9): a pure performance knob on either backend, bit-equal
    to ``balance="none"`` (distributed: dense format only).

``FrozenLDAModel``
    The serving artifact: frozen topic-word counts W + column sum +
    hyperparameters, exportable from any training state or checkpoint and
    ``save``/``load``-able. Its ``transform(docs)`` is a jit-compiled,
    buffer-donated, batched **fold-in Gibbs sampler** that reuses the
    three-branch skip machinery read-only: the per-word amortized
    quantities (top-(g+1) of Ŵ, Q', ΣŴ — ``three_branch.word_stats``) are
    computed ONCE when the model is frozen, because Ŵ never changes at
    serve time. That is WarpLDA's O(1)-per-token view applied to serving:
    each fold-in sweep is O(g) gathers per token for the skip test plus
    the exact sweep only where the bound fails. A whole batch — random
    init, ``n_sweeps`` ESCA sweeps, the θ readout — runs as ONE donated
    dispatch with zero host syncs (pinned by tests/test_serving.py under
    ``jax.transfer_guard``).

Canonical checkpoint format (all backends, all formats)
    ``{"topics_global": (n_tokens,) int32, "key": raw PRNG key data,
    "iteration": int}`` — topics in UNPADDED global token order of the
    engine's prepped corpus. Counts are derived state and get rebuilt on
    restore, which is what makes restores elastic across backends, mesh
    shapes, padding multiples, and live-state formats (dense <-> hybrid,
    single <-> distributed; pinned bit-equal by tests/test_api.py).
    Legacy single-trainer payloads (padded ``"topics"``) still restore.

    Streaming extension (``corpus_residency="streamed"``, DESIGN.md
    SS10): a payload saved MID-EPOCH additionally carries the flat keys
    ``stream_cursor`` (epoch shards already sampled) and
    ``stream_done_topics`` (their post-sample topics); ``topics_global``
    then holds the EPOCH-START assignments the open epoch's counts
    derive from. Epoch-boundary payloads are exactly the canonical
    format, so streamed and resident engines stay interchangeable; a
    mid-epoch payload restores only into a single-host streamed engine
    with the same ``stream_shards`` (and continues bit-identically —
    tests/test_streaming.py).
"""

from __future__ import annotations

import dataclasses
import warnings
from typing import Any, Callable, NamedTuple, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.checkpoint import CheckpointManager
from repro.core import three_branch
from repro.lda.corpus import Corpus, from_documents, relabel_by_frequency
from repro.lda.model import LDAConfig
from repro.lda.trainer import run_boundary_chunked
from repro.runtime import compiles
from repro.runtime.fault import (RestartReport, StepTimer, SupervisePolicy,
                                 is_oom_error, supervised_loop)

__all__ = ["LDAEngine", "FrozenLDAModel", "FoldInBatch", "FoldInResult",
           "SupervisePolicy", "RestartReport"]


# ---------------------------------------------------------------------------
# serving: the frozen artifact + the batched fold-in sampler
# ---------------------------------------------------------------------------

class FoldInBatch(tuple):
    """Device-resident padded token batch for one transform dispatch.

    Built host-side by ``FrozenLDAModel.prepare_batch``; ``word_ids`` is
    DONATED to the fold-in dispatch (its buffer is reused for the returned
    topics), so a batch is consumed by exactly one ``transform_batch``
    call. Both the doc axis and the length axis are bucketed to powers of
    two, which bounds the number of compiled signatures a long-lived
    serving process can accumulate; pad docs/tokens carry mask 0 and
    never touch θ or the LLPT.
    """
    __slots__ = ()

    def __new__(cls, word_ids, doc_ids, mask, n_docs, doc_lens,
                n_real_docs):
        return tuple.__new__(cls, (word_ids, doc_ids, mask, n_docs,
                                   doc_lens, n_real_docs))

    word_ids = property(lambda s: s[0])    # (B*L,) int32, flattened
    doc_ids = property(lambda s: s[1])     # (B*L,) int32 — row index
    mask = property(lambda s: s[2])        # (B*L,) int32 — 1 = real token
    n_docs = property(lambda s: s[3])      # B, bucketed (static)
    doc_lens = property(lambda s: s[4])    # (B_real,) host int64
    n_real_docs = property(lambda s: s[5])  # rows of θ that are real docs


def _next_pow2(n: int, floor: int = 16) -> int:
    return max(floor, 1 << (max(int(n), 1) - 1).bit_length())


class FoldInResult(NamedTuple):
    """One fold-in dispatch's host-side readout."""
    theta: np.ndarray          # (B, K) doc-topic distributions
    llpt: float                # held-out log-likelihood per token (Eq 5)
    frac_skipped: np.ndarray   # (n_sweeps,) phase-1 skip fraction per sweep


def _top_words(W: np.ndarray, word_map: np.ndarray | None,
               k: int) -> np.ndarray:
    """(K, k) most probable word ids per topic, in the ORIGINAL vocab.

    When the engine frequency-relabeled the corpus, W's rows live in
    relabeled space; the inverse map restores user-facing ids.
    """
    top = np.argsort(-W, axis=0, kind="stable")[:k].T        # (K, k)
    if word_map is not None:
        V = W.shape[0]
        new_to_old = np.empty(V, np.int64)
        new_to_old[np.asarray(word_map, np.int64)] = np.arange(V)
        top = new_to_old[top]
    return top


@dataclasses.dataclass(frozen=True, eq=False)
class FrozenLDAModel:
    """Frozen LDA model for serving: W + colsum + hyperparams, read-only.

    ``phi[v][k] = (W[v][k]+β)/(colsum[k]+V·β)`` (== training's Ŵ) is fixed,
    so everything per-word is precomputed at freeze time and fold-in only
    pays per-token work. ``word_map`` carries the engine's
    frequency-relabeling (old id -> model id); ``transform``/``score``
    accept documents in the ORIGINAL vocabulary and remap internally.
    """
    W: np.ndarray                  # (V, K) int32 frozen topic-word counts
    alpha: float
    beta: float
    g: int = 2
    word_map: np.ndarray | None = None   # (V,) int64 old->model ids
    tile_size: int = 8192

    def __post_init__(self):
        W = np.asarray(self.W, np.int32)
        if W.ndim != 2:
            raise ValueError(f"W must be (V, K), got shape {W.shape}")
        object.__setattr__(self, "W", W)
        colsum = W.sum(axis=0, dtype=np.int64)
        V = W.shape[0]
        w_hat = jnp.asarray(
            (W.astype(np.float32) + np.float32(self.beta))
            / (colsum.astype(np.float32) + np.float32(V * self.beta)))
        object.__setattr__(self, "_w_hat", w_hat)
        # The serving amortization: per-word top-(g+1)/Q'/ΣŴ once, forever.
        object.__setattr__(self, "_stats", three_branch.word_stats(
            w_hat, g=self.g, alpha=float(self.alpha)))
        object.__setattr__(self, "_fold_cache", {})

    # -- shape ---------------------------------------------------------------

    @property
    def n_words(self) -> int:
        return int(self.W.shape[0])

    @property
    def n_topics(self) -> int:
        return int(self.W.shape[1])

    # -- constructors --------------------------------------------------------

    @classmethod
    def from_state(cls, state, config: LDAConfig,
                   word_map: np.ndarray | None = None) -> "FrozenLDAModel":
        """Freeze a dense training state (LDAState or anything with .W)."""
        return cls(W=np.asarray(state.W, np.int32), alpha=config.alpha_,
                   beta=config.beta, g=config.g, word_map=word_map,
                   tile_size=config.tile_size)

    @classmethod
    def from_payload(cls, payload: dict[str, Any], corpus: Corpus,
                     config: LDAConfig,
                     word_map: np.ndarray | None = None) -> "FrozenLDAModel":
        """Freeze straight from a canonical checkpoint payload.

        W is derived state: it is rebuilt from (corpus, topics_global) by
        one histogram, so any checkpoint any backend wrote can be served.

        Mid-epoch STREAMED payloads are rejected: their ``topics_global``
        is rewound to the epoch start (the open epoch's sampled shards
        live only in ``stream_done_topics``), so the histogram here would
        silently serve a model that is up to one epoch older than the
        checkpoint's iteration claims.
        """
        if payload.get("stream_cursor") is not None:
            raise ValueError(
                "from_payload got a MID-EPOCH streamed checkpoint "
                f"(stream_cursor={int(payload['stream_cursor'])}): its "
                "topics_global is rewound to the epoch start, so freezing "
                "it would serve stale counts. Resume and finish the epoch "
                "first (engine.restore(payload); engine.fit(1)) and "
                "freeze a boundary state with engine.export(), or publish "
                "a bounded-staleness view through engine.publish_serving()"
                " instead")
        topics = np.asarray(
            _canonical_topics(payload, corpus.n_tokens), np.int32)
        W = np.zeros((corpus.n_words, config.n_topics), np.int32)
        np.add.at(W, (corpus.word_ids, topics), 1)
        return cls(W=W, alpha=config.alpha_, beta=config.beta, g=config.g,
                   word_map=word_map, tile_size=config.tile_size)

    # -- persistence ---------------------------------------------------------

    def save(self, path: str) -> str:
        arrs = {"W": self.W,
                "alpha": np.float64(self.alpha),
                "beta": np.float64(self.beta),
                "g": np.int64(self.g),
                "tile_size": np.int64(self.tile_size)}
        if self.word_map is not None:
            arrs["word_map"] = np.asarray(self.word_map, np.int64)
        with open(path, "wb") as f:
            np.savez(f, **arrs)
        return path

    @classmethod
    def load(cls, path: str) -> "FrozenLDAModel":
        with np.load(path) as z:
            wm = z["word_map"] if "word_map" in z.files else None
            return cls(W=z["W"], alpha=float(z["alpha"]),
                       beta=float(z["beta"]), g=int(z["g"]),
                       word_map=wm, tile_size=int(z["tile_size"]))

    # -- batching ------------------------------------------------------------

    def prepare_batch(self, docs: Sequence[Sequence[int]]) -> FoldInBatch:
        """Pad docs to a (B, L) grid and place it on device.

        L is bucketed to the next power of two (compile-cache friendly);
        pad slots use word 0 with mask 0, so they never touch θ. Word ids
        arrive in the ORIGINAL vocabulary and are remapped through
        ``word_map`` when the engine relabeled.
        """
        if not len(docs):
            raise ValueError("prepare_batch needs at least one document")
        arrs = [np.asarray(d, np.int64).ravel() for d in docs]
        for i, a in enumerate(arrs):
            if a.size and (a.min() < 0 or a.max() >= self.n_words):
                raise ValueError(
                    f"doc {i} has word ids outside [0, {self.n_words}): "
                    "documents must use the training vocabulary")
        if self.word_map is not None:
            wm = np.asarray(self.word_map, np.int64)
            arrs = [wm[a] for a in arrs]
        n_real = len(arrs)
        B = _next_pow2(n_real, floor=8)   # bucketed like L: bounded jit cache
        lens = np.array([a.size for a in arrs], np.int64)
        L = _next_pow2(int(lens.max(initial=1)))
        wid = np.zeros((B, L), np.int32)
        mask = np.zeros((B, L), np.int32)
        for i, a in enumerate(arrs):
            wid[i, :a.size] = a
            mask[i, :a.size] = 1
        doc_ids = np.repeat(np.arange(B, dtype=np.int32), L)
        return FoldInBatch(jnp.asarray(wid.ravel()), jnp.asarray(doc_ids),
                           jnp.asarray(mask.ravel()), B, lens, n_real)

    # -- the fold-in sampler (ONE donated dispatch per batch) ---------------

    def _fold_in_fn(self, n_docs: int, n_tokens: int,
                    n_sweeps: int) -> Callable:
        """Compiled fold-in for one (B, B·L, sweeps) shape signature.

        Per sweep (ESCA semantics, matching training: every token samples
        from the sweep-start counts, then D rebuilds):
          1. phase-1 three-branch skip test from the FROZEN word stats —
             O(g) gathers per token, no O(K) work where the bound holds;
          2. survivor compaction + the exact combined sweep over cond-
             guarded fixed-capacity chunks (training's run_survivor_chunks
             read-only): chunks past the survivor tail cost one predicate,
             so phase-2 work is ceil(survivors/capacity) chunks — skipped
             tokens save REAL compute, exactly as in the fused trainer;
          3. one (B, K) histogram rebuild of the batch's doc-topic counts.
        The sweep keys are prefix-stable (``fold_in(key, s)``), so
        ``n_sweeps=s`` is bit-equal to the first s sweeps of any longer
        run — which is also what lets tests/test_serving.py teacher-force
        the NumPy oracle sweep by sweep.
        """
        sig = (n_docs, n_tokens, n_sweeps)
        fn = self._fold_cache.get(sig)
        if fn is not None:
            return fn
        w_hat, stats_w = self._w_hat, self._stats
        alpha, g, K = float(self.alpha), self.g, self.n_topics
        tile = self.tile_size
        # ~8 active chunks at full survivorship; later sweeps (high skip)
        # run only the occupied prefix. Same shape logic as training's
        # plan_capacity, but static per signature (serving has no EMA).
        capacity = min(n_tokens, _next_pow2(max(n_tokens // 8, 1),
                                            floor=64))
        n_chunks = max(1, -(-n_tokens // capacity))

        def fold_in(key, word_ids, doc_ids, mask):
            kinit, ksweep = jax.random.split(key)
            topics = jax.random.randint(kinit, (n_tokens,), 0, K,
                                        dtype=jnp.int32)
            D = jnp.zeros((n_docs, K), jnp.int32) \
                .at[doc_ids, topics].add(mask)
            n_real = jnp.maximum(jnp.sum(mask), 1).astype(jnp.float32)

            def sweep(carry, s):
                topics, D = carry
                u = jax.random.uniform(jax.random.fold_in(ksweep, s),
                                       (n_tokens,), dtype=jnp.float32)
                dec = three_branch.skip_phase(
                    u, word_ids, doc_ids, D, stats_w, g=g, alpha=alpha)
                rank, n_surv = three_branch.survivor_rank(dec.skip)
                surv_idx = three_branch.compact_survivor_indices(
                    rank, dec.skip, n_chunks * capacity)

                def sample_chunk(idx):
                    return three_branch.exact_three_branch(
                        u[idx], word_ids[idx], doc_ids[idx],
                        stats_w.k[:, 0], D, w_hat, alpha=alpha,
                        tile_size=tile)

                new_topics, _ = three_branch.run_survivor_chunks(
                    surv_idx, n_surv, dec.k1,       # skipped ⇒ K1
                    capacity=capacity, n_chunks=n_chunks,
                    sample_chunk=sample_chunk)
                D = jnp.zeros((n_docs, K), jnp.int32) \
                    .at[doc_ids, new_topics].add(mask)
                frac_skip = jnp.sum(dec.skip * mask) / n_real
                return (new_topics, D), frac_skip

            (topics, D), skips = jax.lax.scan(
                sweep, (topics, D), jnp.arange(n_sweeps))
            len_d = jnp.sum(D, axis=1, dtype=jnp.float32)
            theta = (D.astype(jnp.float32) + alpha) \
                / (len_d[:, None] + K * alpha)
            # Held-out LLPT readout (Eq 5 with the frozen φ == Ŵ): riding
            # inside the dispatch keeps score() sync-free too.
            p = jnp.sum(theta[doc_ids] * w_hat[word_ids], axis=-1)
            ll = jnp.log2(jnp.maximum(p, 1e-30)) * mask
            llpt = jnp.sum(ll) / n_real
            return theta, D, topics, llpt, skips

        # word_ids is donated and consumed: the returned topics alias its
        # buffer (same shape/dtype), so the dispatch allocates no second
        # (B·L,) int32 — the serving analogue of the trainer's donation.
        fn = jax.jit(fold_in, donate_argnums=(1,))
        self._fold_cache[sig] = fn
        return fn

    def transform_batch(self, batch: FoldInBatch, key, *,
                        n_sweeps: int = 20):
        """(θ, D, topics, llpt, per-sweep skip fracs) for a prepared batch.

        ONE donated jit dispatch; every return value is a device array and
        nothing syncs to the host (provable under
        ``jax.transfer_guard("disallow")`` once the shape is compiled).
        ``batch.word_ids`` is consumed (its buffer is donated to the
        returned topics).
        """
        fn = self._fold_in_fn(batch.n_docs, int(batch.word_ids.shape[0]),
                              int(n_sweeps))
        return fn(key, batch.word_ids, batch.doc_ids, batch.mask)

    def fold_in(self, docs: Sequence[Sequence[int]], *, n_sweeps: int = 20,
                seed: int = 0, key=None) -> FoldInResult:
        """θ AND the held-out LLPT AND skip stats from ONE dispatch.

        The single entry point when a caller wants more than one readout:
        transform()/score() are thin views over this, so asking for both
        through fold_in halves the serving work.
        """
        batch = self.prepare_batch(docs)
        if key is None:
            key = jax.random.PRNGKey(seed)
        theta, _, _, llpt, skips = self.transform_batch(batch, key,
                                                        n_sweeps=n_sweeps)
        # drop the bucketing pad rows (uniform θ, zero tokens)
        return FoldInResult(theta=np.asarray(theta)[:batch.n_real_docs],
                            llpt=float(llpt),
                            frac_skipped=np.asarray(skips))

    def transform(self, docs: Sequence[Sequence[int]], *,
                  n_sweeps: int = 20, seed: int = 0,
                  key=None) -> np.ndarray:
        """Fold unseen documents in: (B, K) doc-topic distributions θ.

        θ[d][k] = (D'[d][k]+α)/(len(d)+K·α) where D' comes from
        ``n_sweeps`` Gibbs sweeps against the frozen φ. Bit-reproducible
        for a fixed key/seed.
        """
        return self.fold_in(docs, n_sweeps=n_sweeps, seed=seed,
                            key=key).theta

    def score(self, docs: Sequence[Sequence[int]], *, n_sweeps: int = 20,
              seed: int = 0, key=None) -> float:
        """Held-out log-likelihood per token (Eq 5) under the frozen φ."""
        return self.fold_in(docs, n_sweeps=n_sweeps, seed=seed,
                            key=key).llpt

    # -- introspection -------------------------------------------------------

    def top_words(self, k: int = 10) -> np.ndarray:
        """(K, k) most probable word ids per topic, in the ORIGINAL vocab."""
        return _top_words(self.W, self.word_map, k)


# ---------------------------------------------------------------------------
# the canonical checkpoint payload
# ---------------------------------------------------------------------------

def _canonical_topics(payload: dict[str, Any], n_tokens: int,
                      padded_len: int | None = None) -> np.ndarray:
    """Unpadded global-order topics from a canonical OR legacy payload.

    A legacy (padded ``"topics"``) payload is accepted only when its length
    is exactly ``n_tokens`` or exactly ``padded_len`` (the restoring
    trainer's padded length, when known) — the same strictness as the old
    trainer-level shape check, so a payload from a different corpus never
    silently truncates into garbage counts.
    """
    if "topics_global" in payload:
        tg = np.asarray(payload["topics_global"], np.int32)
        if tg.shape[0] != n_tokens:
            raise ValueError(
                f"checkpoint topics_global has {tg.shape[0]} entries but "
                f"the corpus holds {n_tokens} tokens: the checkpoint "
                "belongs to a different corpus")
        return tg
    if "topics" in payload:
        tg = np.asarray(payload["topics"], np.int32)
        if tg.shape[0] != n_tokens and (padded_len is None
                                        or tg.shape[0] != padded_len):
            want = f"{n_tokens}" if padded_len is None \
                else f"{n_tokens} (unpadded) or {padded_len} (padded)"
            raise ValueError(
                f"legacy checkpoint topics have {tg.shape[0]} entries; "
                f"expected {want}: the checkpoint belongs to a different "
                "corpus or tiling")
        return tg[:n_tokens]
    raise ValueError(
        "checkpoint payload has neither 'topics_global' (canonical) "
        f"nor 'topics' (legacy): keys = {sorted(payload)}")


class _CanonicalManager:
    """Checkpoint-manager adapter: canonical payloads on disk, backend
    payloads in memory.

    The single trainer speaks padded ``"topics"``; this wrapper converts to
    the unpadded canonical format on save and back on restore, so every
    backend's checkpoints are interchangeable without the trainers knowing.
    """

    def __init__(self, inner: CheckpointManager, to_canonical: Callable,
                 from_canonical: Callable):
        self.inner = inner
        self._to = to_canonical
        self._from = from_canonical

    def save(self, step: int, payload: dict[str, Any]) -> str:
        return self.inner.save(step, self._to(payload))

    def restore_latest(self) -> dict[str, Any] | None:
        payload = self.inner.restore_latest()
        return None if payload is None else self._from(payload)


# ---------------------------------------------------------------------------
# backends (internal: the old trainers behind the one surface)
# ---------------------------------------------------------------------------

class _SingleBackend:
    """LDATrainer behind the engine surface (one host, dense or hybrid)."""

    name = "single"

    def __init__(self, corpus: Corpus | None, config: LDAConfig,
                 manager: CheckpointManager | None):
        from repro.lda.trainer import LDATrainer
        self.corpus = corpus
        self.config = config
        wrapped = None
        if manager is not None:
            wrapped = _CanonicalManager(manager, self._to_canonical,
                                        self._from_canonical)
        self.trainer = LDATrainer(corpus, config, checkpoint_manager=wrapped,
                                  _from_engine=True)
        # disk residency has no resident corpus; token geometry comes
        # from the CorpusStore manifest via the trainer
        self._n_tokens = self.trainer.n_real_tokens
        self._n_padded = self.trainer.n_padded_tokens

    # payload conversion (trainer speaks padded "topics"; the streaming
    # extension keys ride through both directions unchanged)

    def _to_canonical(self, payload: dict[str, Any]) -> dict[str, Any]:
        from repro.train.lda_step import STREAM_PAYLOAD_KEYS
        out = {"topics_global": np.asarray(payload["topics"], np.int32)
               [:self._n_tokens],
               "key": payload["key"], "iteration": payload["iteration"]}
        for k in STREAM_PAYLOAD_KEYS:
            if k in payload:
                out[k] = payload[k]
        return out

    def _from_canonical(self, payload: dict[str, Any]) -> dict[str, Any]:
        from repro.train.lda_step import STREAM_PAYLOAD_KEYS
        tg = _canonical_topics(payload, self._n_tokens,
                               padded_len=self._n_padded)
        padded = np.zeros(self._n_padded, np.int32)
        padded[:self._n_tokens] = tg
        out = {"topics": padded, "key": payload["key"],
               "iteration": payload["iteration"]}
        for k in STREAM_PAYLOAD_KEYS:
            if k in payload:
                out[k] = payload[k]
        return out

    def _as_lda_state(self, state):
        """StreamState (epoch boundary) -> LDAState; LDAState passes
        through. A mid-epoch StreamState raises the pipeline's
        actionable boundary error."""
        from repro.train.lda_step import StreamState
        if isinstance(state, StreamState):
            return self.trainer.fused_pipeline().to_lda_state(state)
        return state

    # lifecycle
    def restore_or_init(self):
        return self.trainer.restore_or_init()

    def state_from_canonical(self, payload: dict[str, Any]):
        return self.trainer.state_from_payload(self._from_canonical(payload))

    def canonical_payload(self, state) -> dict[str, Any]:
        from repro.train.lda_step import StreamState
        if isinstance(state, StreamState):
            # the streaming pipeline emits canonical payloads natively
            # (including the mid-epoch stream_* extension keys)
            return self.trainer.fused_pipeline().stream_payload(state)
        return self._to_canonical(state.host_payload())

    def run(self, n_iters: int, state, log_fn, checkpoint_every,
            on_chunk=None):
        return self.trainer.run(n_iters, state, log_fn, checkpoint_every,
                                on_chunk=on_chunk)

    def evaluate(self, state) -> float:
        from repro.train.lda_step import StreamState
        if isinstance(state, StreamState) \
                and self.trainer.residency == "disk":
            # paged shard-fold LLPT: never densifies W (bitwise equal to
            # the resident evaluate — pinned in tests/test_streaming.py)
            return self.trainer._evaluate_stream(state)
        return self.trainer.evaluate(self._as_lda_state(state))

    def dense_W(self, state) -> np.ndarray:
        return np.asarray(self._as_lda_state(state).W, np.int32)

    def serving_W(self, state) -> tuple:
        """``(W, cursor, n_shards)``: a bounded-staleness serving view of
        ANY state — a mid-epoch StreamState exports ``W0 + ΔW`` (epoch-
        start counts plus the sampled shards' moves), boundary and dense
        states export exact counts at cursor 0."""
        from repro.train.lda_step import StreamState
        if isinstance(state, StreamState):
            return self.trainer.fused_pipeline().serving_counts(state)
        return self.dense_W(state), 0, 1

    def live_serving_W(self):
        return self.trainer.live_serving_W()

    def state_nbytes(self, state) -> int:
        from repro.train.lda_step import StreamState
        if isinstance(state, StreamState):
            # measure the LIVE streamed representation (counts tuple);
            # _as_lda_state would densify W and misreport paged modes
            return self.trainer.live_state_nbytes(state)
        return self.trainer.live_state_nbytes(self._as_lda_state(state))


class _DistBackend:
    """The multi-device trainers behind the engine surface.

    ``config.dist.w_sync`` picks the W synchronization strategy —
    ``"replicate"`` (DistLDATrainer: full replica + delta all-reduce)
    or ``"ps"`` (PSDistTrainer: word-sharded parameter server with
    stale-synchronous pulls/pushes). Both speak the same state surface
    (init/run_fused/host_payload/gather_global), so everything below
    this constructor is strategy-agnostic.
    """

    name = "distributed"

    def __init__(self, corpus: Corpus, config: LDAConfig,
                 manager: CheckpointManager | None, mesh,
                 pad_multiple: int = 1024):
        from repro.lda.distributed import DistLDATrainer, PSDistTrainer
        dc = config.dist
        if mesh is None:
            from repro.runtime.compat import make_mesh
            if dc.mesh_shape:
                mesh = make_mesh(tuple(int(e) for _, e in dc.mesh_shape),
                                 tuple(a for a, _ in dc.mesh_shape))
            else:
                mesh = make_mesh((jax.device_count(), 1),
                                 ("data", "model"))
        elif dc.mesh_shape:
            raise ValueError(
                "pass mesh= OR DistConfig.mesh_shape, not both: two mesh "
                "specifications with different extents would silently "
                "disagree")
        self.corpus = corpus
        self.config = config
        self.manager = manager
        self.is_ps = dc.w_sync == "ps"
        cls = PSDistTrainer if self.is_ps else DistLDATrainer
        self.trainer = cls(corpus, config, mesh,
                           pad_multiple=pad_multiple,
                           _from_engine=True)

    def restore_or_init(self):
        if self.manager is not None:
            payload = self.manager.restore_latest()
            if payload is not None:
                return self.state_from_canonical(payload)
        return self.trainer.init_state()

    def state_from_canonical(self, payload: dict[str, Any]):
        # the dist trainers' native payload IS the canonical format; the
        # stream_* extension keys must ride through so the trainer's
        # mid-epoch guard fires instead of silently resuming from the
        # epoch start, and the ps_* keys so a PS restore rebuilds the
        # open round (the replicated trainer ignores them — redoing the
        # round from the cut is bit-identical, the interchange contract)
        from repro.checkpoint.ps_payload import PS_PAYLOAD_PREFIX
        from repro.train.lda_step import STREAM_PAYLOAD_KEYS
        native = {"topics_global": _canonical_topics(payload,
                                                     self.corpus.n_tokens),
                  "key": payload["key"], "iteration": payload["iteration"]}
        for k in STREAM_PAYLOAD_KEYS:
            if k in payload:
                native[k] = payload[k]
        for k in payload:
            if k.startswith(PS_PAYLOAD_PREFIX):
                native[k] = payload[k]
        return self.trainer.state_from_payload(native)

    def canonical_payload(self, state) -> dict[str, Any]:
        return self.trainer.host_payload(state)

    def evaluate(self, state) -> float:
        return self.trainer.llpt(state)

    def run(self, n_iters: int, state, log_fn, checkpoint_every,
            on_chunk=None):
        """Boundary-chunked scan loop: the multi-device mirror of
        LDATrainer.run_fused — same shared driver, so same history
        schema, eval cadence, and checkpoint timing by construction."""
        tr = self.trainer
        carry = {"s": state}
        self._live = carry

        def run_chunk(chunk):
            carry["s"], stats = tr.run_fused(carry["s"], chunk)
            jax.block_until_ready(carry["s"].topics)
            if self.config.selfcheck:
                with jax.profiler.TraceAnnotation("lda.selfcheck"):
                    tr.selfcheck(carry["s"])
            return stats

        try:
            history = run_boundary_chunked(
                n_iters, int(state.iteration),
                n_tokens=self.corpus.n_tokens,
                eval_every=self.config.eval_every,
                checkpoint_every=checkpoint_every,
                run_chunk=run_chunk,
                evaluate=lambda: self.evaluate(carry["s"]),
                save=None if self.manager is None else
                lambda it: self.manager.save(
                    it, self.canonical_payload(carry["s"])),
                log_fn=log_fn,
                on_chunk=on_chunk)
        finally:
            self._live = None
        return carry["s"], history

    def dense_W(self, state) -> np.ndarray:
        _, W = self.trainer.gather_global(state)
        return np.asarray(W, np.int32)

    def serving_W(self, state) -> tuple:
        # distributed live states publish at chunk boundaries, which are
        # always epoch boundaries for the dist pipeline — exact counts
        return self.dense_W(state), 0, 1

    def live_serving_W(self):
        live = getattr(self, "_live", None)
        if live is None:
            return None
        return self.serving_W(live["s"])

    def state_nbytes(self, state) -> int:
        return self.trainer.state_nbytes(state)


# ---------------------------------------------------------------------------
# the engine
# ---------------------------------------------------------------------------

class LDAEngine:
    """The single public entry point for EZLDA training and serving.

    >>> engine = LDAEngine(corpus, LDAConfig(n_topics=64))
    >>> engine.fit(100)
    >>> model = engine.export()          # FrozenLDAModel
    >>> theta = model.transform(new_docs)

    Backends: ``"single"`` (LDATrainer — dense or hybrid fused pipeline)
    and ``"distributed"`` (shard_map over a device mesh; within it,
    ``config.dist.w_sync`` picks ``"replicate"`` — DistLDATrainer, full
    W replica + delta all-reduce — or ``"ps"`` — PSDistTrainer, the
    word-sharded parameter server with stale-synchronous pulls);
    ``"auto"`` picks distributed iff more than one device is visible, a
    mesh (or ``DistConfig.mesh_shape``) is passed, or ``w_sync="ps"`` is
    requested. All backends share the canonical checkpoint format, so an
    engine can restore any engine's checkpoints regardless of backend,
    live-state format, w_sync strategy, mesh, or padding.
    """

    def __init__(self, corpus: Corpus | Sequence[Sequence[int]] | None,
                 config: LDAConfig, *, backend: str = "auto", mesh=None,
                 checkpoint_dir: str | None = None,
                 checkpoint_manager: CheckpointManager | None = None,
                 pad_multiple: int = 1024, n_words: int | None = None):
        if backend not in ("auto", "single", "distributed"):
            raise ValueError(f"unknown backend {backend!r}: expected "
                             "'auto', 'single', or 'distributed'")
        if checkpoint_dir is not None and checkpoint_manager is not None:
            raise ValueError("pass checkpoint_dir OR checkpoint_manager, "
                             "not both")
        # -- corpus prep (the engine owns it) -------------------------------
        from repro.train.lda_step import resolves_to_disk
        if resolves_to_disk(config):
            # Disk-native (also "auto" + corpus_path, which resolves to
            # disk): the CorpusStore at config.corpus_path is the corpus.
            # It was written from an already-prepped (frequency-
            # relabeled, word-sorted) stream, so re-prepping here would
            # silently disagree with the shard files on disk.
            if corpus is not None:
                raise ValueError(
                    "corpus_residency='disk' trains from the CorpusStore "
                    f"at corpus_path={config.corpus_path!r}: pass "
                    "corpus=None (the store already holds the prepped "
                    "token stream; write one with "
                    "ShardedCorpus.to_store())")
            self.word_map = None
            self.corpus = None
        elif corpus is None:
            raise ValueError(
                "corpus=None needs corpus_residency='disk' with "
                "corpus_path set: otherwise the engine has no tokens "
                "to train on")
        else:
            if not isinstance(corpus, Corpus):
                docs = [np.asarray(d, np.int64) for d in corpus]
                if n_words is None:
                    n_words = int(max((int(d.max()) for d in docs if d.size),
                                      default=-1)) + 1
                corpus = from_documents(docs, n_words)
            self.word_map = None
            counts = np.asarray(corpus.word_token_counts)
            if counts.size and np.any(np.diff(counts) > 0):
                # the hybrid layout REQUIRES the frequency relabeling and
                # every other path tolerates it, so prep applies it
                # uniformly; the map is kept so serving can speak the
                # original vocabulary
                corpus, self.word_map = relabel_by_frequency(corpus)
            self.corpus = corpus
        self.config = config
        if checkpoint_dir is not None:
            checkpoint_manager = CheckpointManager(checkpoint_dir)
        self.checkpoint_manager = checkpoint_manager

        # -- backend selection (re-runnable: _rebuild_backend re-enters it
        #    after a supervised restart, picking up device-count changes) --
        self._backend_arg = backend
        self._mesh = mesh
        self._pad_multiple = pad_multiple
        self._device_count = jax.device_count()
        self._backend = self._make_backend()
        self._state = None
        self.restart_report: RestartReport | None = None
        self.history: dict[str, list] = {"iteration": [], "llpt": [],
                                         "tokens_per_sec": [], "stats": []}
        self._subscribers: list[Callable] = []
        self._serving_seq = 0

    def _make_backend(self):
        from repro.train.lda_step import resolves_to_disk
        backend, mesh = self._backend_arg, self._mesh
        dc = self.config.dist
        if backend == "auto":
            # an explicit mesh, a DistConfig mesh_shape, or w_sync="ps"
            # is an explicit request for the distributed backends; disk
            # residency is single-backend by construction, so auto never
            # routes it to shard_map even on multi-device hosts
            wants_dist = (mesh is not None or bool(dc.mesh_shape)
                          or dc.w_sync == "ps")
            if resolves_to_disk(self.config) and not wants_dist:
                backend = "single"
            else:
                backend = "distributed" if (wants_dist
                                            or jax.device_count() > 1) \
                    else "single"
        if backend == "single" and dc.w_sync == "ps":
            raise ValueError(
                "DistConfig(w_sync='ps') needs the distributed backend: "
                "the parameter server shards W across data-parallel "
                "workers (drop backend='single' or w_sync='ps')")
        self.backend_name = backend
        if resolves_to_disk(self.config) and backend == "distributed":
            raise ValueError(
                "corpus_residency='disk' needs the single backend: the "
                "paged streaming pipeline owns the device transfer "
                "schedule, which shard_map's static partitioning cannot "
                "express (pass backend='single')")
        if backend == "single":
            if mesh is not None:
                raise ValueError("backend='single' does not take a mesh")
            return _SingleBackend(self.corpus, self.config,
                                  self.checkpoint_manager)
        return _DistBackend(self.corpus, self.config,
                            self.checkpoint_manager, mesh,
                            pad_multiple=self._pad_multiple)

    def _rebuild_backend(self, report: RestartReport | None = None) -> None:
        """Re-run backend selection (supervised recovery path).

        Counts are derived state and the checkpoint format is canonical,
        so a restart is elastic: if the visible device count changed, the
        rebuilt backend re-shards onto whatever is there now.
        """
        new_count = jax.device_count()
        if new_count != self._device_count:
            if report is not None:
                report.elastic_reshards.append((self._device_count,
                                                new_count))
            self._device_count = new_count
        self._backend = self._make_backend()

    # -- introspection -------------------------------------------------------

    @property
    def trainer(self):
        """The internal backend trainer (benchmarks / advanced use)."""
        return self._backend.trainer

    @property
    def state(self):
        if self._state is None:
            raise RuntimeError("no training state yet: call fit() or "
                               "resume() first")
        return self._state

    @property
    def iteration(self) -> int:
        return int(self.state.iteration)

    def state_nbytes(self) -> int:
        """Measured live count-state bytes of the CURRENT representation."""
        return self._backend.state_nbytes(self.state)

    # -- lifecycle -----------------------------------------------------------

    def fit(self, n_iters: int, *, log_fn: Callable[[str], None] | None = None,
            checkpoint_every: int | None = None,
            supervise: SupervisePolicy | bool | None = None
            ) -> dict[str, list]:
        """Train for n_iters (resuming from the engine's current state, a
        checkpoint if one exists, or a fresh init). Returns this call's
        history; ``engine.history`` accumulates across calls.

        ``supervise=SupervisePolicy(...)`` (or ``True`` for the defaults)
        turns the call into a supervised run: restartable faults (see
        ``SupervisePolicy.restartable``) trigger restore-from-newest-valid-
        checkpoint with bounded exponential backoff instead of crashing,
        an OOM on the resident path degrades once to streamed residency,
        and the returned history carries a ``"restart_report"`` entry
        (also ``engine.restart_report``). Requires a checkpoint manager.

        ``history["lowered"]`` lists the programs JAX lowered during the
        call, in order (``repro.runtime.compiles``); a program lowered
        after the call's first iteration is also named through ``log_fn``.
        """
        lowered = compiles.mark()
        if supervise is not None and supervise is not False:
            policy = SupervisePolicy() if supervise is True else supervise
            hist = self._fit_supervised(n_iters, policy, log_fn=log_fn,
                                        checkpoint_every=checkpoint_every)
            hist["lowered"] = compiles.since(lowered)
            self.history.setdefault("lowered", []).extend(hist["lowered"])
            return hist
        if self._state is None:
            self._state = self._backend.restore_or_init()
        self._state, hist = self._backend.run(
            n_iters, self._state, log_fn, checkpoint_every,
            on_chunk=(self._publish_live if self._subscribers else None))
        if self._subscribers:
            self.publish_serving()      # final state after the run
        hist["lowered"] = compiles.since(lowered)
        for k, v in hist.items():
            self.history.setdefault(k, []).extend(v)
        return hist

    def _fit_supervised(self, n_iters: int, policy: SupervisePolicy, *,
                        log_fn: Callable[[str], None] | None = None,
                        checkpoint_every: int | None = None
                        ) -> dict[str, list]:
        """fit() under a restart supervisor (DESIGN.md §11).

        Each attempt restores from the newest VALID checkpoint (corrupt
        ones are walked past), replays deterministically, and — because
        restore + replay is bit-identical to never having crashed — the
        final state matches an uninterrupted run bitwise. With
        ``policy.checkpoint_shards`` set (single streamed backend only),
        checkpoints are cut every k shards MID-epoch via the stream
        payload extension; step keys are scaled to ``it*(S+1)+cursor`` so
        they stay monotonic against epoch-boundary saves.
        """
        import time as _time

        from repro.runtime import chaos
        from repro.train.lda_step import StreamState

        if self.checkpoint_manager is None:
            raise ValueError("fit(supervise=...) needs checkpoint_dir or "
                             "checkpoint_manager: restart recovery is "
                             "restore-from-checkpoint")
        shardwise = policy.checkpoint_shards is not None
        ps_shardwise = shardwise and getattr(self._backend, "is_ps", False)
        if shardwise and not ps_shardwise and not (
                self.backend_name == "single"
                and getattr(self._backend.trainer, "residency", None)
                in ("streamed", "disk")):
            raise ValueError(
                "SupervisePolicy.checkpoint_shards needs the single "
                "streamed or disk backend (corpus_residency='streamed' "
                "or 'disk') or the distributed parameter-server backend "
                "(DistConfig(w_sync='ps')): mid-epoch payloads only "
                "exist on the streaming pipelines")
        ckpt_every = checkpoint_every or policy.checkpoint_every
        report = RestartReport(completed_steps=0, restarts=0,
                               resumed_from=[])
        timer = StepTimer(window=policy.straggler_window,
                          z_threshold=policy.straggler_z)
        target: dict[str, int | None] = {"v": None}
        merged: dict[str, list] = {"iteration": [], "llpt": [],
                                   "tokens_per_sec": [], "stats": []}
        seen_iters: set[int] = set()

        def merge_hist(hist: dict[str, list]) -> None:
            # restarts replay iterations; dedup so history stays monotone
            for i, it in enumerate(hist["iteration"]):
                if it in seen_iters:
                    continue
                seen_iters.add(it)
                for k in merged:
                    merged[k].append(hist[k][i])

        def ensure_state() -> None:
            if self._state is None:
                payload = self.checkpoint_manager.restore_latest(
                    log_fn=log_fn)
                if payload is not None:
                    self._state = self._backend.state_from_canonical(
                        payload)
                    report.resumed_from.append(self.iteration)
                else:
                    self._state = self._backend.restore_or_init()
            if target["v"] is None:
                target["v"] = self.iteration + n_iters

        def on_chunk(it: int, chunk: int, dt: float) -> None:
            if timer.record(dt / max(chunk, 1)):
                report.straggler_steps.append(it)
            self._publish_live(it, chunk, dt)

        def attempt_run() -> None:
            ensure_state()
            remaining = target["v"] - self.iteration
            if remaining <= 0:
                return
            self._state, hist = self._backend.run(
                remaining, self._state, log_fn, ckpt_every,
                on_chunk=on_chunk)
            merge_hist(hist)

        def attempt_shardwise() -> None:
            ensure_state()
            pipe = self._backend.trainer.fused_pipeline()
            mgr = self.checkpoint_manager
            S = pipe.stream.n_shards
            k = int(policy.checkpoint_shards)
            # a fresh init (or boundary restore) arrives as LDAState;
            # from_lda_state converts it and passes StreamState through
            ss = pipe.from_lda_state(self._state)
            assert isinstance(ss, StreamState)
            first = not merged["iteration"]
            while int(ss.iteration) < target["v"]:
                if chaos.armed():
                    chaos.step_range(int(ss.iteration), 1)
                ep_t0 = _time.perf_counter()
                while ss.cursor < S:
                    t0 = _time.perf_counter()
                    ss = pipe.run_shards(ss, k)
                    self._state = ss
                    dt = _time.perf_counter() - t0
                    step_key = int(ss.iteration) * (S + 1) + ss.cursor
                    if timer.record(dt / max(min(k, S), 1)):
                        report.straggler_steps.append(step_key)
                    if ss.cursor < S:       # boundary save covers cursor==S
                        mgr.save(step_key, pipe.stream_payload(ss))
                    if self._subscribers:   # mid-epoch bounded-staleness view
                        Wv, cur, n_sh = pipe.serving_counts(ss)
                        self._notify(Wv, cur, n_sh, int(ss.iteration))
                ss, stats, _ = pipe.run_fused(ss, 1)   # close the epoch
                self._state = ss
                if self._subscribers:       # exact epoch-boundary view
                    Wv, cur, n_sh = pipe.serving_counts(ss)
                    self._notify(Wv, cur, n_sh, int(ss.iteration))
                dt = _time.perf_counter() - ep_t0
                it = int(ss.iteration)
                mgr.save(it * (S + 1), pipe.stream_payload(ss))
                if it % self.config.eval_every == 0 or first:
                    first = False
                    last = {kk: float(np.ravel(v)[-1])
                            for kk, v in stats._asdict().items()}
                    n_tok = self._backend.trainer.n_real_tokens
                    merge_hist({"iteration": [it],
                                "llpt": [self._backend.evaluate(ss)],
                                "tokens_per_sec": [n_tok / dt],
                                "stats": [last]})
                    if log_fn:
                        log_fn(f"iter={it:4d} llpt={merged['llpt'][-1]:+.4f}"
                               f" tok/s={n_tok / dt:,.0f}")

        def attempt_shardwise_ps() -> None:
            # the PS trainer's mid-epoch surface: lockstep sub-shard
            # groups (aligned clocks), ps_* extension payloads at every
            # cut, step keys on the same it*(R+1)+cursor grid as the
            # single streamed path
            ensure_state()
            tr = self._backend.trainer
            mgr = self.checkpoint_manager
            R = tr._R
            k = int(policy.checkpoint_shards)
            ss = self._state
            first = not merged["iteration"]
            denom = float(max(int(tr.sc.mask.sum()), 1))
            while int(ss.iteration) < target["v"]:
                it0 = int(ss.iteration)
                if chaos.armed():
                    chaos.step_range(it0, 1)
                ep_t0 = _time.perf_counter()
                while int(ss.iteration) == it0:
                    t0 = _time.perf_counter()
                    ss = tr.run_shards(ss, k)
                    self._state = ss
                    dt = _time.perf_counter() - t0
                    cur = int(ss.cursors.max())
                    step_key = int(ss.iteration) * (R + 1) + cur
                    if timer.record(dt / max(min(k, R), 1)):
                        report.straggler_steps.append(step_key)
                    if int(ss.iteration) == it0 and cur > 0:
                        mgr.save(step_key, tr.host_payload(ss))
                dt = _time.perf_counter() - ep_t0
                it = int(ss.iteration)
                mgr.save(it * (R + 1), tr.host_payload(ss))
                if self._subscribers:   # aligned clock == exact counts
                    self._notify(self._backend.dense_W(ss), 0, 1, it)
                _ns, sums = ss.stat_rounds.pop(it0, (0, np.zeros(5)))
                if it % self.config.eval_every == 0 or first:
                    first = False
                    m = np.asarray(sums, np.float64) / denom
                    n_tok = self.corpus.n_tokens
                    merge_hist({"iteration": [it],
                                "llpt": [self._backend.evaluate(ss)],
                                "tokens_per_sec": [n_tok / dt],
                                "stats": [{
                                    "frac_skipped": float(m[0]),
                                    "frac_m_final": float(m[1]),
                                    "frac_unchanged": float(m[2]),
                                    "frac_at_max": float(m[3]),
                                    "frac_q_branch": 0.0,
                                    "frac_phase2_slots": float(m[4])}]})
                    if log_fn:
                        log_fn(f"iter={it:4d} llpt={merged['llpt'][-1]:+.4f}"
                               f" tok/s={n_tok / dt:,.0f}")

        def recover(exc: BaseException) -> None:
            self._state = None      # next attempt restores from checkpoint
            if is_oom_error(exc) and not report.degraded_to_streamed \
                    and self.config.corpus_residency \
                    not in ("streamed", "disk"):
                warnings.warn(
                    "supervised fit hit an out-of-memory fault on the "
                    f"resident path ({exc}); degrading once to "
                    "corpus_residency='streamed' and restoring from the "
                    "newest checkpoint", RuntimeWarning, stacklevel=2)
                self.config = dataclasses.replace(
                    self.config, corpus_residency="streamed")
                report.degraded_to_streamed = True
            self._rebuild_backend(report)

        attempt = attempt_run
        if shardwise:
            attempt = attempt_shardwise_ps if ps_shardwise \
                else attempt_shardwise
        supervised_loop(attempt, recover, policy, report)
        if not shardwise and self.iteration % ckpt_every != 0:
            self.checkpoint_manager.save(
                self.iteration, self._backend.canonical_payload(self._state))
        report.completed_steps = self.iteration
        report.timer_summary = timer.summary
        self.restart_report = report
        for k, v in merged.items():
            self.history.setdefault(k, []).extend(v)
        out: dict[str, Any] = dict(merged)
        out["restart_report"] = report
        return out

    def resume(self) -> "LDAEngine":
        """Restore the newest checkpoint into the engine (explicit resume).

        Requires a checkpoint manager/dir; falls back to a fresh init when
        no checkpoint exists yet. Returns self (chainable)."""
        if self.checkpoint_manager is None:
            raise ValueError("resume() needs checkpoint_dir or "
                             "checkpoint_manager")
        self._state = self._backend.restore_or_init()
        return self

    def score(self) -> float:
        """Training-corpus LLPT (Eq 5) at the current state."""
        return self._backend.evaluate(self.state)

    # -- checkpoints ---------------------------------------------------------

    def host_payload(self) -> dict[str, Any]:
        """The canonical checkpoint payload for the current state."""
        return self._backend.canonical_payload(self.state)

    def save(self) -> str:
        if self.checkpoint_manager is None:
            raise ValueError("save() needs checkpoint_dir or "
                             "checkpoint_manager")
        return self.checkpoint_manager.save(self.iteration,
                                            self.host_payload())

    def restore(self, payload: dict[str, Any]) -> "LDAEngine":
        """Adopt a canonical (or legacy) payload as the current state."""
        self._state = self._backend.state_from_canonical(payload)
        return self

    # -- serving -------------------------------------------------------------

    def subscribe(self, fn: Callable) -> Callable[[], None]:
        """Register ``fn(ServingSnapshot)``; returns an unsubscribe
        callable.

        Subscribers receive one snapshot per publish point: every chunk
        boundary during ``fit()`` (plus a final one when the run
        returns), every ``run_shards`` group under shard-wise
        supervision (a MID-epoch bounded-staleness view, cursor > 0),
        and every explicit ``publish_serving()``. ``repro.serve.attach``
        wires a snapshot stream into a running ``LDAService``.
        """
        self._subscribers.append(fn)

        def unsubscribe() -> None:
            try:
                self._subscribers.remove(fn)
            except ValueError:
                pass
        return unsubscribe

    def publish_serving(self):
        """Snapshot the CURRENT state — exact counts at a boundary, the
        ``W0 + ΔW`` bounded-staleness view mid-epoch — deliver it to all
        subscribers, and return it (a ``ServingSnapshot``)."""
        W, cursor, n_shards = self._backend.serving_W(self.state)
        return self._notify(W, cursor, n_shards, self.iteration)

    def _notify(self, W, cursor, n_shards, iteration):
        from repro.serve.refresh import ServingSnapshot
        self._serving_seq += 1
        snap = ServingSnapshot(
            W=np.ascontiguousarray(W, np.int32), alpha=self.config.alpha_,
            beta=self.config.beta, g=self.config.g,
            iteration=int(iteration), cursor=int(cursor),
            n_shards=int(n_shards), seq=self._serving_seq,
            word_map=self.word_map, tile_size=self.config.tile_size)
        for fn in list(self._subscribers):
            fn(snap)
        return snap

    def _publish_live(self, iteration: int, chunk: int = 1,
                      dt: float = 0.0) -> None:
        """``on_chunk``-shaped publish hook: snapshot the backend's live
        in-run state (quiescent at chunk boundaries) if anyone listens."""
        if not self._subscribers:
            return
        view = self._backend.live_serving_W()
        if view is None:
            return
        self._notify(view[0], view[1], view[2], iteration)

    def export(self) -> FrozenLDAModel:
        """Freeze the current state into the serving artifact."""
        return FrozenLDAModel(
            W=self._backend.dense_W(self.state), alpha=self.config.alpha_,
            beta=self.config.beta, g=self.config.g, word_map=self.word_map,
            tile_size=self.config.tile_size)

    def top_words(self, k: int = 10) -> np.ndarray:
        """(K, k) top word ids per topic at the current state (original
        vocab) — straight from the counts, no serving artifact built."""
        return _top_words(self._backend.dense_W(self.state), self.word_map,
                          k)
