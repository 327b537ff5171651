"""Single-host LDA trainer: sample -> update -> eval loop.

Drives either the two-branch (ESCA baseline) or the three-branch (EZLDA)
sampler over a corpus. Multi-device training lives in lda/distributed.py and
reuses the same per-shard step functions.

Two execution modes share one state/checkpoint format:
  * step(): the reference path — sample, full count rebuild, one dispatch
    per phase. The semantics oracle.
  * run_fused()/run(..with config.fused..): the fused pipeline from
    train/lda_step.py — single donated dispatch per scanned stretch,
    incremental delta count updates, no per-iteration host syncs. Produces
    bit-identical topics/counts to step() for the same key.
"""

from __future__ import annotations

import time
from typing import Any, Callable

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import esca, llpt as llpt_mod
from repro.lda import invariants
from repro.lda.corpus import Corpus, pad_corpus
from repro.lda.model import LDAConfig, LDAState
from repro.runtime import chaos, compiles

__all__ = ["LDATrainer", "chunk_to_boundary", "run_boundary_chunked"]


def chunk_to_boundary(it_now: int, done: int, remaining: int,
                      eval_every: int,
                      checkpoint_every: int | None = None) -> int:
    """Iterations to scan before the next absolute eval/ckpt boundary.

    Shared by LDATrainer.run_fused and the engine's distributed loop so
    both backends hit the SAME boundaries (same history shape) for the
    same config: resumed runs (start % eval_every != 0) and non-divisible
    n_iters still land on every boundary a stepwise loop would. The first
    chunk is a single iteration — a baseline eval is recorded after it,
    and history must not change shape when the loop flavor changes.
    """
    if done == 0:
        return min(1, remaining)
    chunk = eval_every - it_now % eval_every
    if checkpoint_every:
        chunk = min(chunk, checkpoint_every - it_now % checkpoint_every)
    return min(chunk, remaining)


def run_boundary_chunked(n_iters: int, start_iter: int, *, n_tokens: int,
                         eval_every: int, checkpoint_every: int | None,
                         run_chunk: Callable, evaluate: Callable,
                         save: Callable | None,
                         log_fn: Callable[[str], None] | None,
                         on_chunk: Callable | None = None) -> dict:
    """The ONE boundary-chunked driver both backends run fit() through.

    ``run_chunk(chunk) -> stacked stats`` advances the caller's carried
    state by ``chunk`` iterations (blocking until done — the dt here is
    real device time); ``evaluate() -> float`` scores the current carry;
    ``save(it)`` checkpoints it. Eval cadence, history schema, log format,
    and checkpoint timing live only here, so the single and distributed
    backends cannot drift apart (the engine's same-history-shape
    contract).

    ``on_chunk(it, chunk, dt)`` (optional) observes every chunk's wall
    time — the fit supervisor's straggler detector rides here without
    changing the chunking or paying extra host syncs. Being the one
    driver, this is also where step-indexed chaos faults fire.

    Each chunk runs inside an ``lda.chunk`` step span on the profiler's
    clock, with ``lda.eval``, ``lda.stats`` and ``lda.checkpoint`` spans
    inside it; a program lowered after the first chunk is named through
    ``log_fn``.
    """
    history: dict[str, list] = {"iteration": [], "llpt": [],
                                "tokens_per_sec": [], "stats": []}
    late = compiles.LateLowerings(log_fn)
    done = 0
    while done < n_iters:
        chunk = chunk_to_boundary(start_iter + done, done, n_iters - done,
                                  eval_every, checkpoint_every)
        with jax.profiler.StepTraceAnnotation("lda.chunk",
                                              step_num=start_iter + done):
            t0 = time.perf_counter()
            # inside the timed window: an injected slow step shows up in
            # its own chunk's wall time (the straggler detector's test
            # surface)
            if chaos.armed():
                chaos.step_range(start_iter + done, chunk)
            stats = run_chunk(chunk)
            dt = time.perf_counter() - t0
            done += chunk
            it = start_iter + done
            if on_chunk is not None:
                on_chunk(it, chunk, dt)
            if it % eval_every == 0 or done == chunk:
                with jax.profiler.TraceAnnotation("lda.eval"):
                    score = evaluate()
                with jax.profiler.TraceAnnotation("lda.stats"):
                    last = {k: float(np.ravel(v)[-1])
                            for k, v in stats._asdict().items()}
                history["iteration"].append(it)
                history["llpt"].append(score)
                history["tokens_per_sec"].append(n_tokens * chunk / dt)
                history["stats"].append(last)
                if log_fn:
                    log_fn(f"iter={it:4d} llpt={score:+.4f} "
                           f"tok/s={n_tokens*chunk/dt:,.0f} "
                           f"unchanged={last.get('frac_unchanged', 0):.3f}")
            if checkpoint_every and save is not None \
                    and it % checkpoint_every == 0:
                with jax.profiler.TraceAnnotation("lda.checkpoint"):
                    save(it)
        late.settle(it)
    return history


class LDATrainer:
    """Owns device arrays for one corpus and jit-compiled step functions.

    Engine-internal: this is the ``backend="single"`` backend of
    ``repro.lda.api.LDAEngine``, which owns corpus prep, backend
    selection, and the unified checkpoint format. Direct construction
    raises TypeError (it warned for one release; the engine is the only
    front door now).
    """

    def __init__(self, corpus: Corpus | None, config: LDAConfig,
                 checkpoint_manager: Any | None = None, *,
                 _from_engine: bool = False):
        if not _from_engine:
            raise TypeError(
                "LDATrainer is an engine-internal backend: construct "
                "through repro.lda.api.LDAEngine(corpus, config, "
                "backend='single') — it wraps this trainer with unified "
                "checkpoints and the serving export path")
        self.config = config
        self.checkpoint_manager = checkpoint_manager
        self._fused_pipeline = None
        from repro.train.lda_step import resolves_to_disk
        if resolves_to_disk(config):
            # Disk-native residency (DESIGN.md SS14): the CorpusStore's
            # shard files ARE the corpus — tokens never materialize in
            # host RAM as one array, and W pages per shard. The trainer
            # holds only the store handle plus shape metadata.
            from repro.lda.storage import CorpusStore
            self.store = CorpusStore.open(config.corpus_path)
            if self.store.shard_len % config.tile_size != 0:
                raise ValueError(
                    f"CorpusStore shard_len {self.store.shard_len} is not "
                    f"a multiple of tile_size {config.tile_size}: rewrite "
                    "the store from a stream sharded with "
                    "multiple=tile_size, or change tile_size")
            self.corpus = None
            self.word_ids = self.doc_ids = self.mask = None
            self.n_docs = self.store.n_docs
            self.n_words = self.store.n_words
            self.n_real_tokens = self.store.n_tokens
            self.n_padded_tokens = self.store.n_padded
            from repro.train.lda_step import resolve_residency
            self.residency, self.n_stream_shards = resolve_residency(
                config, self.store.n_padded)
            self._sampler = None
            return
        self.store = None
        corpus.validate()
        self.corpus = corpus
        padded, mask = pad_corpus(corpus, config.tile_size)
        from repro.train.lda_step import resolve_residency
        self.residency, self.n_stream_shards = resolve_residency(
            config, padded.n_tokens)
        # Streamed residency keeps the token arrays HOST-side: the
        # streaming pipeline moves one epoch shard at a time; only the
        # occasional full-array consumers (init/restore histograms, LLPT
        # eval) upload them transiently.
        as_array = np.asarray if self.residency == "streamed" else \
            jnp.asarray
        self.word_ids = as_array(padded.word_ids)
        self.doc_ids = as_array(padded.doc_ids)
        self.mask = as_array(mask)
        self.n_docs = corpus.n_docs
        self.n_words = corpus.n_words
        self.n_real_tokens = corpus.n_tokens
        self.n_padded_tokens = int(padded.word_ids.shape[0])
        if config.sampler == "warp" and config.impl == "pallas":
            # the fused warp chunk falls back to a full-vocabulary window,
            # so the whole vocabulary must fit the kernel's VMEM bound
            from repro.kernels.sample_warp import check_window
            check_window(self.n_words, config.n_topics)
        self._sampler = self._make_sampler()

    # -- state ------------------------------------------------------------

    def init_state(self) -> LDAState:
        key = jax.random.PRNGKey(self.config.seed)
        key, sub = jax.random.split(key)
        if self.residency == "disk":
            # Same draw as init_counts — one split, one randint over the
            # padded slot count — so a disk trainer with the same seed
            # starts bitwise equal to a resident one. The counts are then
            # folded shard-by-shard on the host (int adds == the device
            # scatter exactly) by state_from_stream_payload.
            topics = jax.random.randint(
                sub, (self.n_padded_tokens,), 0, self.config.n_topics,
                dtype=jnp.int32)
            pipe = self.fused_pipeline()
            return pipe.state_from_stream_payload({
                "topics_global":
                    np.asarray(topics)[:self.n_real_tokens],
                "key": np.asarray(jax.random.key_data(key)),
                "iteration": 0,
            })
        topics, D, W = esca.init_counts(
            sub, self.word_ids, self.doc_ids, self.mask,
            n_docs=self.n_docs, n_words=self.n_words,
            n_topics=self.config.n_topics)
        return LDAState(topics=topics, D=D, W=W, key=key,
                        iteration=jnp.int32(0))

    def restore_or_init(self) -> LDAState:
        if self.checkpoint_manager is not None:
            payload = self.checkpoint_manager.restore_latest()
            if payload is not None:
                return self.state_from_payload(payload)
        return self.init_state()

    def host_payload(self, state: LDAState) -> dict[str, Any]:
        return state.host_payload()

    def state_from_payload(self, payload: dict[str, Any]) -> LDAState:
        if self.residency == "disk":
            # Disk-native: every restore (boundary or mid-epoch) re-enters
            # through the streaming pipeline — there is no resident token
            # array to histogram against.
            from repro.train.lda_step import STREAM_PAYLOAD_KEYS
            pipe = self.fused_pipeline()
            topics = np.asarray(payload["topics"], np.int32)
            canonical = {"topics_global": topics[:self.n_real_tokens],
                         "key": payload["key"],
                         "iteration": payload["iteration"]}
            canonical.update({k: payload[k] for k in STREAM_PAYLOAD_KEYS
                              if k in payload})
            return pipe.state_from_stream_payload(canonical)
        if int(np.asarray(payload.get("stream_cursor", 0))) > 0:
            # mid-epoch streaming payload (docs/API.md checkpoint schema):
            # only the streaming pipeline can re-open the epoch
            if self.residency != "streamed":
                raise ValueError(
                    "checkpoint was saved mid-epoch by a streamed trainer "
                    f"(stream_cursor={int(payload['stream_cursor'])}): "
                    "restore it with corpus_residency='streamed' (and the "
                    "same stream_shards), or re-save it at an epoch "
                    "boundary")
            from repro.train.lda_step import STREAM_PAYLOAD_KEYS
            pipe = self.fused_pipeline()
            topics = np.asarray(payload["topics"], np.int32)
            canonical = {"topics_global": topics[:self.corpus.n_tokens],
                         "key": payload["key"],
                         "iteration": payload["iteration"]}
            canonical.update({k: payload[k] for k in STREAM_PAYLOAD_KEYS
                              if k in payload})
            return pipe.state_from_stream_payload(canonical)
        topics = jnp.asarray(payload["topics"], jnp.int32)
        if topics.shape != self.word_ids.shape:
            raise ValueError(
                f"checkpoint topics have shape {tuple(topics.shape)} but "
                f"this trainer's padded corpus has "
                f"{tuple(self.word_ids.shape)} token slots: the checkpoint "
                "was written for a different corpus or tile_size. Restore "
                "through repro.lda.api.LDAEngine, whose canonical payload "
                "stores topics in unpadded global token order and re-pads "
                "for whatever tiling the restoring trainer uses")
        D, W = esca.update_counts(
            self.word_ids, self.doc_ids, topics, self.mask,
            n_docs=self.n_docs, n_words=self.n_words,
            n_topics=self.config.n_topics)
        key = jax.random.wrap_key_data(jnp.asarray(payload["key"]))
        return LDAState(topics=topics, D=D, W=W, key=key,
                        iteration=jnp.int32(payload["iteration"]))

    # -- steps ------------------------------------------------------------

    def _make_sampler(self) -> Callable:
        cfg = self.config
        if cfg.sampler == "warp":
            # WarpLDA-style MH engine (core/mh.py, DESIGN.md SS12). The
            # stepwise reference path rebuilds the alias tables from the
            # LIVE Ŵ every iteration (zero staleness); the fused pipeline
            # is where the scan-start snapshot + Pallas tile build live —
            # impl="pallas" therefore routes through run()/run_fused.
            from repro.core import mh
            index = mh.build_doc_index(self.doc_ids, self.mask,
                                       self.n_docs)
            self.doc_index = index

            def sampler(key, state):
                W_hat = esca.compute_w_hat(state.W, cfg.beta)
                tables = mh.build_alias_tables(W_hat)
                return mh.sample_warp(
                    key, self.word_ids, self.doc_ids, state.topics,
                    state.D, W_hat, tables, index, alpha=cfg.alpha_,
                    n_cycles=cfg.mh_cycles, mask=self.mask)
        elif cfg.impl == "pallas":
            from repro.kernels import ops as kops
            def sampler(key, state):
                W_hat = esca.compute_w_hat(state.W, cfg.beta)
                return kops.sample_tokens(
                    key, self.word_ids, self.doc_ids, state.topics,
                    state.D, W_hat, alpha=cfg.alpha_, tile_size=cfg.tile_size)
        elif cfg.sampler == "two_branch":
            def sampler(key, state):
                W_hat = esca.compute_w_hat(state.W, cfg.beta)
                return esca.sample_two_branch(
                    key, self.word_ids, self.doc_ids, state.topics,
                    state.D, W_hat, alpha=cfg.alpha_, tile_size=cfg.tile_size)
        elif cfg.sampler == "three_branch":
            from repro.core import three_branch
            plan = three_branch.build_plan(self.corpus, cfg)
            self.plan = plan
            def sampler(key, state):
                return three_branch.sample(
                    key, plan, self.word_ids, self.doc_ids, state.topics,
                    state.D, state.W, cfg)
        else:
            raise ValueError(f"unknown sampler {cfg.sampler!r}")
        return sampler

    def step(self, state: LDAState) -> tuple[LDAState, dict[str, Any]]:
        cfg = self.config
        if self._sampler is None:
            raise ValueError(
                "the stepwise reference path needs the token arrays "
                "resident; corpus_residency='disk' trains only through "
                "run()/run_fused (the streaming pipeline)")
        key, sub = jax.random.split(state.key)
        new_topics, stats = self._sampler(sub, state)
        D, W = esca.update_counts(
            self.word_ids, self.doc_ids, new_topics, self.mask,
            n_docs=self.n_docs, n_words=self.n_words, n_topics=cfg.n_topics)
        new_state = LDAState(topics=new_topics, D=D, W=W, key=key,
                             iteration=state.iteration + 1)
        return new_state, dict(stats._asdict())

    def fused_pipeline(self):
        """Lazily built fused pipeline (dense or hybrid, per config.format).

        Both expose the same surface (from_lda_state/to_lda_state/step/
        run_fused); with ``format="hybrid"`` the live training state between
        dispatches is the packed SparseLDAState instead of dense D/W.
        """
        if self._fused_pipeline is None:
            from repro.train.lda_step import (FusedPipeline,
                                              HybridFusedPipeline,
                                              StreamingHybridPipeline,
                                              StreamingPipeline)
            if self.residency == "disk":
                # The CorpusStore IS the stream: same shard grid surface
                # as a ShardedCorpus, but reads come from the file layer
                # and the pipelines page W per shard.
                if self.config.format == "hybrid":
                    self._fused_pipeline = StreamingHybridPipeline(
                        self.store, n_docs=self.n_docs,
                        n_words=self.n_words, config=self.config,
                        corpus=self.store.corpus_meta())
                else:
                    self._fused_pipeline = StreamingPipeline(
                        self.store, n_docs=self.n_docs,
                        n_words=self.n_words, config=self.config)
            elif self.residency == "streamed":
                from repro.lda.corpus import shard_stream
                stream = shard_stream(self.corpus, self.n_stream_shards,
                                      multiple=self.config.tile_size)
                if self.config.format == "hybrid":
                    self._fused_pipeline = StreamingHybridPipeline(
                        stream, n_docs=self.n_docs, n_words=self.n_words,
                        config=self.config, corpus=self.corpus)
                else:
                    self._fused_pipeline = StreamingPipeline(
                        stream, n_docs=self.n_docs, n_words=self.n_words,
                        config=self.config)
            elif self.config.format == "hybrid":
                self._fused_pipeline = HybridFusedPipeline(
                    self.word_ids, self.doc_ids, self.mask,
                    n_docs=self.n_docs, n_words=self.n_words,
                    config=self.config, corpus=self.corpus)
            else:
                self._fused_pipeline = FusedPipeline(
                    self.word_ids, self.doc_ids, self.mask,
                    n_docs=self.n_docs, n_words=self.n_words,
                    config=self.config)
        return self._fused_pipeline

    def live_state_nbytes(self, state: LDAState) -> int:
        """Measured count-state bytes of the LIVE training representation.

        For format="hybrid" this converts through the pipeline's layout and
        measures the actual packed buffers (what Table I now reports),
        not an analytic byte model.
        """
        from repro.train.lda_step import StreamState
        if self.config.format == "hybrid":
            fs = self.fused_pipeline().from_lda_state(state)
            if hasattr(fs, "nbytes"):
                return fs.nbytes()
            # streamed hybrid: measure the packed count tuple directly
            return sum(int(a.nbytes) for a in jax.tree.leaves(fs.counts))
        if isinstance(state, StreamState):
            return sum(int(a.nbytes) for a in jax.tree.leaves(state.counts))
        return state.nbytes()

    def evaluate(self, state: LDAState) -> float:
        from repro.train.lda_step import StreamState
        if isinstance(state, StreamState) and self.residency == "disk":
            return self._evaluate_stream(state)
        score = float(llpt_mod.llpt(
            self.word_ids, self.doc_ids, self.mask, state.D, state.W,
            alpha=self.config.alpha_, beta=self.config.beta,
            tile_size=self.config.tile_size))
        if self.config.selfcheck and not np.isfinite(score):
            raise invariants.InvariantViolation(
                "finite_llpt", f"evaluate (iteration "
                f"{int(state.iteration)})", f"llpt={score!r}")
        return score

    def _evaluate_stream(self, ss) -> float:
        """LLPT folded over the stream's shards with a paged W window —
        bitwise equal to evaluate() on the densified state (DESIGN.md
        SS14): identical per-token values through the identical compiled
        reduce."""
        score = float(self.fused_pipeline().eval_llpt(ss))
        if self.config.selfcheck and not np.isfinite(score):
            raise invariants.InvariantViolation(
                "finite_llpt", f"evaluate (iteration "
                f"{int(ss.iteration)})", f"llpt={score!r}")
        return score

    # -- loop -------------------------------------------------------------

    def run_fused(self, n_iters: int, state: LDAState | None = None,
                  log_fn: Callable[[str], None] | None = None,
                  checkpoint_every: int | None = None, *,
                  on_chunk: Callable | None = None) -> tuple[LDAState, dict]:
        """Fused loop: eval-free stretches run as ONE scanned dispatch.

        Iterations between eval/checkpoint boundaries never touch the host;
        the survivor EMA re-plans chunk capacity only between scans.
        """
        state = self.restore_or_init() if state is None else state
        pipe = self.fused_pipeline()
        carry = {"fs": pipe.from_lda_state(state)}
        selfcheck = self.config.selfcheck
        self._live = carry      # chunk-boundary handle for live_serving_W

        def run_chunk(chunk):
            carry["fs"], stats, _ = pipe.run_fused(carry["fs"], chunk)
            jax.block_until_ready(carry["fs"].topics)
            if selfcheck:
                with jax.profiler.TraceAnnotation("lda.selfcheck"):
                    pipe.selfcheck(carry["fs"])
            return stats

        if self.residency == "disk":
            # Never densify for eval or save: LLPT folds over the store's
            # shards with a paged W window, and checkpoints carry the
            # global topic stream instead of a padded resident array.
            evaluate = lambda: self._evaluate_stream(carry["fs"])  # noqa: E731
            save_payload = lambda: self._stream_host_payload(  # noqa: E731
                carry["fs"])
        else:
            evaluate = lambda: self.evaluate(  # noqa: E731
                pipe.to_lda_state(carry["fs"]))
            save_payload = lambda: pipe.to_lda_state(  # noqa: E731
                carry["fs"]).host_payload()
        try:
            history = run_boundary_chunked(
                n_iters, int(state.iteration),
                n_tokens=self.n_real_tokens,
                eval_every=self.config.eval_every,
                checkpoint_every=checkpoint_every,
                run_chunk=run_chunk,
                evaluate=evaluate,
                save=None if self.checkpoint_manager is None else
                lambda it: self.checkpoint_manager.save(
                    it, save_payload()),
                log_fn=log_fn, on_chunk=on_chunk)
        finally:
            self._live = None
        if self.residency == "disk":
            return carry["fs"], history
        return pipe.to_lda_state(carry["fs"]), history

    def _stream_host_payload(self, ss) -> dict[str, Any]:
        """Trainer checkpoint payload for a live stream state.

        Same schema as ``LDAState.host_payload`` — ``topics`` is the
        GLOBAL (unpadded) token stream here; disk restores re-slice it
        through ``state_from_stream_payload`` — plus the stream-cursor
        keys when saved mid-epoch."""
        pipe = self.fused_pipeline()
        payload = pipe.stream_payload(ss)
        payload["topics"] = payload.pop("topics_global")
        return payload

    def live_serving_W(self):
        """``(W, cursor, n_shards)`` of the LIVE in-run state, or None
        outside a run. Mid-epoch streamed states export the bounded-
        staleness ``W0 + ΔW`` view (``serving_counts``); boundary and
        dense states export exact counts at cursor 0. Read at chunk
        boundaries only (the ``on_chunk`` hook) — that is the one point
        where the live carry is quiescent."""
        from repro.train.lda_step import StreamState
        live = getattr(self, "_live", None)
        if live is None:
            return None
        fs = live.get("fs", live.get("state"))
        if fs is None:
            return None
        if isinstance(fs, StreamState):
            return self.fused_pipeline().serving_counts(fs)
        if not hasattr(fs, "W"):        # hybrid packed: densify
            fs = self.fused_pipeline().to_lda_state(fs)
        return np.asarray(fs.W, np.int32), 0, 1

    def run(self, n_iters: int, state: LDAState | None = None,
            log_fn: Callable[[str], None] | None = None,
            checkpoint_every: int | None = None, *,
            on_chunk: Callable | None = None) -> tuple[LDAState, dict]:
        # The hybrid live state only exists inside the fused pipeline, and
        # a streamed corpus only exists as the pipeline's epoch shards; the
        # per-iteration step() stays the dense resident semantics oracle.
        if self.config.fused or self.config.format == "hybrid" \
                or self.residency in ("streamed", "disk"):
            return self.run_fused(n_iters, state, log_fn, checkpoint_every,
                                  on_chunk=on_chunk)
        state = self.restore_or_init() if state is None else state
        history: dict[str, list] = {"iteration": [], "llpt": [],
                                    "tokens_per_sec": [], "stats": []}
        start_iter = int(state.iteration)
        live: dict = {"state": state}
        self._live = live
        try:
            state, history = self._run_stepwise(
                state, history, start_iter, n_iters, live,
                log_fn, checkpoint_every, on_chunk)
        finally:
            self._live = None
        return state, history

    def _run_stepwise(self, state, history, start_iter, n_iters, live,
                      log_fn, checkpoint_every, on_chunk):
        """One ``step()`` per iteration, each inside an ``lda.iteration``
        step span on the profiler's clock, with ``lda.selfcheck``,
        ``lda.eval``, ``lda.stats`` (the stats' device-to-host pull) and
        ``lda.checkpoint`` spans inside it. A program lowered after the
        first iteration is named through ``log_fn``."""
        late = compiles.LateLowerings(log_fn)
        for i in range(start_iter, start_iter + n_iters):
            with jax.profiler.StepTraceAnnotation("lda.iteration",
                                                  step_num=i):
                state = self._stepwise_iteration(
                    state, history, i, start_iter, live, log_fn,
                    checkpoint_every, on_chunk)
            late.settle(i + 1)
        return state, history

    def _stepwise_iteration(self, state, history, i, start_iter, live,
                            log_fn, checkpoint_every, on_chunk):
        t0 = time.perf_counter()
        if chaos.armed():
            chaos.step_range(i, 1)
        state, stats = self.step(state)
        live["state"] = state
        jax.block_until_ready(state.topics)
        dt = time.perf_counter() - t0
        if self.config.selfcheck:
            with jax.profiler.TraceAnnotation("lda.selfcheck"):
                invariants.check_dense_counts(
                    state.D, state.W, n_tokens=self.corpus.n_tokens,
                    where=f"step (iteration {i + 1})")
        if on_chunk is not None:
            on_chunk(i + 1, 1, dt)
        if (i + 1) % self.config.eval_every == 0 or i == start_iter:
            with jax.profiler.TraceAnnotation("lda.eval"):
                score = self.evaluate(state)
            with jax.profiler.TraceAnnotation("lda.stats"):
                last = {k: float(np.asarray(v)) for k, v in stats.items()}
            history["iteration"].append(i + 1)
            history["llpt"].append(score)
            history["tokens_per_sec"].append(self.corpus.n_tokens / dt)
            history["stats"].append(last)
            if log_fn:
                log_fn(f"iter={i+1:4d} llpt={score:+.4f} "
                       f"tok/s={self.corpus.n_tokens/dt:,.0f} "
                       f"unchanged={last.get('frac_unchanged', 0):.3f}")
        if (checkpoint_every and self.checkpoint_manager is not None
                and (i + 1) % checkpoint_every == 0):
            with jax.profiler.TraceAnnotation("lda.checkpoint"):
                self.checkpoint_manager.save(int(state.iteration),
                                             state.host_payload())
        return state
