"""Data-parallel serving replicas, each owning its own donated dispatch.

A ``Replica`` is one device-pinned copy of the serving tables (via
``HotWordCache``) plus its own compiled fold-in kernels, so N replicas
never serialize on one jit cache or one device queue. ``ReplicaSet``
round-robins replicas over the visible devices — or over a mesh's device
grid when one is passed (``runtime/sharding.py`` sizes the default
replica count from the mesh's batch axes, the same axes the distributed
trainer data-parallelizes over).

The dispatch itself is the serving-optimized variant of
``FrozenLDAModel``'s fold-in (DESIGN.md SS13):

  * **token packing** — a micro-batch is ONE flat token list (docs
    concatenated, total length pow2-bucketed) instead of the batch API's
    (B, L) grid, so one 3-token query in a batch with one 300-token query
    no longer pays 300 slots; doc-count buckets stay pow2 for the same
    bounded-jit-cache reason.
  * **alias warm start** — the initial topics are drawn from the frozen
    φ_w through the per-word alias tables (``core/mh.word_proposals``
    machinery, O(1) per token) instead of uniformly, which cuts the
    sweeps needed to reach the fold-in LLPT plateau from ~5 to ~2
    (measured on XLA:CPU).
  * the sweep body is exactly the batch API's ESCA semantics: phase-1
    skip test from the frozen word stats, survivor compaction, the exact
    combined sweep over cond-guarded chunks, one doc-histogram rebuild.

Per-batch keys are the caller's business (the service derives
``fold_in(key, batch_seq)``); a fixed key + fixed batch composition is
bit-reproducible across replicas, devices, and cache configurations.
"""

from __future__ import annotations

import threading
from typing import Callable, NamedTuple, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import mh, three_branch
from repro.runtime import chaos, sharding
from repro.serve.cache import HotWordCache

__all__ = ["Replica", "ReplicaSet", "ReplicaDead"]


class ReplicaDead(RuntimeError):
    """The targeted replica was killed (chaos or shutdown)."""


def _next_pow2(n: int, floor: int = 8) -> int:
    p = floor
    while p < n:
        p *= 2
    return p


_TOKEN_GRANULE = 4096


def _pad_tokens(total: int, floor: int) -> int:
    """Token-slot bucket: pow2 up to 4 granules, then granule multiples.

    Strict pow2 wastes up to ~50% of every sweep's token lanes (a 20480-
    token batch pays 32768); above 16384 the signature set stays small
    enough that 4096-granule buckets bound the waste at one granule
    without unbounding the jit cache.
    """
    n = max(total, 1)
    if n <= 4 * _TOKEN_GRANULE:
        return _next_pow2(n, floor=floor)
    return -(-n // _TOKEN_GRANULE) * _TOKEN_GRANULE


class PackedBatch(NamedTuple):
    """Flat token layout for one micro-batch (host-side)."""
    word_ids: np.ndarray        # (N,) int64 MODEL-vocab ids (remapped)
    doc_ids: np.ndarray         # (N,) int32, pad tokens -> doc 0, mask 0
    mask: np.ndarray            # (N,) int32
    n_docs: int                 # padded doc-slot count (pow2 bucket)
    n_real_docs: int


def pack_docs(docs: Sequence[Sequence[int]], *, n_words: int,
              word_map: np.ndarray | None, doc_buckets: Sequence[int],
              token_floor: int = 256) -> PackedBatch:
    """Concatenate docs into one flat pow2-padded token list.

    Documents arrive in the ORIGINAL vocabulary and are remapped through
    ``word_map`` exactly like ``FrozenLDAModel.prepare_batch``; pad slots
    use word 0 / doc 0 with mask 0, so they never touch θ.
    """
    if not len(docs):
        raise ValueError("pack_docs needs at least one document")
    arrs = [np.asarray(d, np.int64).ravel() for d in docs]
    n_real = len(arrs)
    B = next((b for b in doc_buckets if b >= n_real),
             _next_pow2(n_real, floor=max(doc_buckets)))
    lens = np.array([a.size for a in arrs], np.int64)
    total = int(lens.sum())
    N = _pad_tokens(total, token_floor)
    word_ids = np.zeros(N, np.int64)
    doc_ids = np.zeros(N, np.int32)
    mask = np.zeros(N, np.int32)
    if total:
        flat = np.concatenate(arrs)
        # validate the flat list in one pass; name the offending doc
        # only on the (cold) failure path
        if flat.min() < 0 or flat.max() >= n_words:
            bad = next(i for i, a in enumerate(arrs) if a.size
                       and (a.min() < 0 or a.max() >= n_words))
            raise ValueError(
                f"doc {bad} has word ids outside [0, {n_words}): "
                "documents must use the training vocabulary")
        # ONE remap gather over the flat list, not one per doc
        word_ids[:total] = flat if word_map is None \
            else np.asarray(word_map, np.int64)[flat]
    doc_ids[:total] = np.repeat(np.arange(n_real, dtype=np.int32), lens)
    mask[:total] = 1
    return PackedBatch(word_ids, doc_ids, mask, B, n_real)


class Replica:
    """One device-pinned serving worker: tables + compiled dispatches."""

    def __init__(self, rid: int, model, *, device=None,
                 hot_words: int | None = None, warm_start: bool = True,
                 tile_size: int | None = None):
        self.rid = rid
        self.device = device
        self.alive = True
        self.n_words = model.n_words
        self.n_topics = model.n_topics
        self.word_map = model.word_map
        self.g = model.g
        self.alpha = float(model.alpha)
        self.tile_size = int(tile_size or model.tile_size)
        self.warm_start = bool(warm_start)
        self.cache = HotWordCache(model, hot_words=hot_words,
                                  warm_start=warm_start, device=device)
        self._fold_cache: dict[tuple, Callable] = {}
        self.batches_done = 0

    # -- the packed fold-in dispatch -----------------------------------------

    def _fold_in_fn(self, n_docs: int, n_tokens: int, n_sweeps: int,
                    n_rows: int, has_tail: bool,
                    with_llpt: bool) -> Callable:
        sig = (n_docs, n_tokens, n_sweeps, n_rows, has_tail, with_llpt)
        fn = self._fold_cache.get(sig)
        if fn is not None:
            return fn
        alpha, g, K = self.alpha, self.g, self.n_topics
        tile, warm = self.tile_size, self.warm_start
        n_per = 6 + (2 if warm else 0)   # args per table block
        capacity = min(n_tokens, _next_pow2(max(n_tokens // 8, 1),
                                            floor=64))
        n_chunks = max(1, -(-n_tokens // capacity))

        def fold_in(key, seq, word_ids, doc_ids, mask, *table_args):
            # per-batch key derivation rides INSIDE the dispatch — the
            # eager fold_in would cost the host two extra device ops on
            # every micro-batch
            key = jax.random.fold_in(key, seq)
            if has_tail:
                # hot block (device-resident across batches) + this
                # batch's padded tail slice, concatenated INSIDE the jit:
                # per-batch host work stays a numpy gather, and the tail
                # upload rides the dispatch instead of eager device ops
                table_args = tuple(
                    jnp.concatenate([h, t]) for h, t in
                    zip(table_args[:n_per], table_args[n_per:]))
            w_hat, a, k, k12, q_prime, wsum, *alias_args = table_args
            stats_w = three_branch.WordStats(a, k, k12, q_prime, wsum)
            kinit, ksweep = jax.random.split(key)
            if warm:
                prob, alias = alias_args
                u0 = jax.random.uniform(kinit, (1, 2, n_tokens),
                                        dtype=jnp.float32)
                topics = mh.alias_draw(u0, word_ids, prob, alias,
                                       n_topics=K)[0]
            else:
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
                    surv_idx, n_surv, dec.k1,
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
            if with_llpt:
                p = jnp.sum(theta[doc_ids] * w_hat[word_ids], axis=-1)
                ll = jnp.log2(jnp.maximum(p, 1e-30)) * mask
                llpt = jnp.sum(ll) / n_real
            else:
                # serving wants θ only; the diagnostic readout is an
                # extra n_tokens x K contraction the hot path skips
                llpt = jnp.float32(0.0)
            # topics rides out so the donated word_ids buffer has an
            # (int32, n_tokens) output to alias — callers drop it
            return theta, llpt, skips, topics

        # word_ids donated: the returned topics scratch aliases its
        # buffer inside the dispatch — same discipline as the batch API
        fn = jax.jit(fold_in, donate_argnums=(2,))
        self._fold_cache[sig] = fn
        return fn

    def infer_packed(self, packed: PackedBatch, key, *,
                     n_sweeps: int, seq: int = 0,
                     with_llpt: bool = True
                     ) -> tuple[np.ndarray, float, dict]:
        """(θ rows for the real docs, batch llpt, accounting dict).

        ``seq`` is folded into ``key`` inside the dispatch (the service
        passes its batch sequence number); ``with_llpt=False`` compiles
        the serving variant that skips the diagnostic LLPT readout.
        """
        if not self.alive:
            raise ReplicaDead(f"replica {self.rid} is dead")
        asm = self.cache.assemble(packed.word_ids)
        n_tokens = int(packed.word_ids.shape[0])
        dev = self.device
        put = (lambda x: jax.device_put(x, dev)) if dev is not None \
            else jnp.asarray
        wid = put(asm.local_ids)
        did = put(packed.doc_ids)
        msk = put(packed.mask)
        fn = self._fold_in_fn(packed.n_docs, n_tokens, int(n_sweeps),
                              asm.n_rows, bool(asm.tail_args),
                              bool(with_llpt))
        theta, llpt, _skips, _topics = fn(key, np.int32(seq), wid, did,
                                          msk, *asm.tables.as_args(),
                                          *asm.tail_args)
        self.batches_done += 1
        return (np.asarray(theta)[:packed.n_real_docs], float(llpt),
                {"cache_hits": asm.hits, "cache_misses": asm.misses,
                 "padded_tokens": n_tokens,
                 "padded_docs": packed.n_docs})

    def refresh(self, W: np.ndarray) -> None:
        """Adopt a new W snapshot (tear-free: see HotWordCache.refresh).

        Compiled fold-in kernels survive — tables are jit ARGUMENTS, so a
        swap never pays a retrace."""
        self.cache.refresh(W)

    def kill(self) -> None:
        self.alive = False


class ReplicaSet:
    """N replicas round-robined over devices, swapped as one unit."""

    def __init__(self, model, *, n_replicas: int = 1, mesh=None,
                 hot_words: int | None = None, warm_start: bool = True):
        if mesh is not None:
            devices = list(np.asarray(mesh.devices).ravel())
            if n_replicas <= 0:
                # one replica per data-parallel slot, the same axes the
                # distributed trainer batches over
                n_replicas = sharding.mesh_axis_size(
                    mesh, sharding.batch_axes(mesh))
        else:
            devices = jax.devices()
        n_replicas = max(int(n_replicas), 1)
        # a single device serves every replica when that is all there is
        # (thread-level parallelism still overlaps host prep with device
        # dispatch); multiple devices round-robin
        assign = [devices[i % len(devices)] for i in range(n_replicas)]
        if len(devices) == 1:
            assign = [None] * n_replicas     # default device: no pinning
        self.replicas = [
            Replica(i, model, device=assign[i], hot_words=hot_words,
                    warm_start=warm_start)
            for i in range(n_replicas)]
        self._lock = threading.Lock()

    def __len__(self) -> int:
        return len(self.replicas)

    @property
    def alive(self) -> list[Replica]:
        return [r for r in self.replicas if r.alive]

    def swap(self, W: np.ndarray) -> None:
        """Refresh every replica to a new W snapshot (built off the
        serving path, per-replica pointer swap — in-flight batches keep
        the tables they captured)."""
        with self._lock:
            for r in self.replicas:
                if r.alive:
                    r.refresh(W)

    def chaos_event(self, rid: int) -> str | None:
        """Poll the chaos harness for this replica (no-op un-armed)."""
        if not chaos.armed():
            return None
        return chaos.replica_event(rid)

    def cache_hit_rate(self) -> float | None:
        hits = sum(r.cache.hits for r in self.replicas)
        tok = hits + sum(r.cache.misses for r in self.replicas)
        return hits / tok if tok else None
