"""Out-of-core streaming tests (corpus_residency="streamed", DESIGN.md SS10).

The load-bearing properties:
  1. ``shard_stream`` shards exactly cover the padded token stream — no
     token lost or duplicated — and every shard's word-run metadata and
     inverted-index slice are consistent with its token slice
     (hypothesis property tests over random corpora).
  2. Streamed training is BITWISE equal to the resident fused path for
     dense × hybrid formats on the single-host backend and for
     dense × hybrid on the distributed backend, and composes with
     ``balance="tiles"`` and ``impl="pallas"`` unchanged.
  3. Mid-epoch checkpoints (stream_cursor / stream_done_topics) restore
     into a fresh pipeline and continue bit-identically, through the
     pipeline, the CheckpointManager npz round-trip, and the engine.
  4. The residency auto-policy streams exactly when estimated token
     bytes exceed the budget, and the shard planner respects the
     double-buffer window math.
"""

import numpy as np
import pytest

import jax

from tests._hyp import given, settings, st
from repro.lda.corpus import pad_corpus, shard_stream
from repro.lda.model import LDAConfig
from repro.lda.trainer import LDATrainer
from repro.train.lda_step import (STREAM_BYTES_PER_TOKEN,
                                  plan_stream_shards, resolve_residency)

jax.config.update("jax_platform_name", "cpu")


def _docs_strategy():
    return st.lists(
        st.lists(st.integers(min_value=0, max_value=29), min_size=0,
                 max_size=12),
        min_size=1, max_size=25)


# ---------------------------------------------------------------------------
# 1. ShardedCorpus invariants (property tests)
# ---------------------------------------------------------------------------

@settings(max_examples=40, deadline=None)
@given(docs=_docs_strategy(),
       n_shards=st.integers(min_value=1, max_value=6),
       multiple=st.sampled_from([1, 8, 32]))
def test_shard_stream_exactly_covers_t(docs, n_shards, multiple):
    """No token lost/duplicated: the masked shard slots, in shard order,
    are exactly the padded stream's real tokens, which are exactly T."""
    from repro.lda.corpus import from_documents
    corpus = from_documents([np.asarray(d, np.int64) for d in docs], 30)
    sc = shard_stream(corpus, n_shards, multiple=multiple)
    sc.validate(deep=True)      # incl. the lazy inverted-index slices
    padded, mask = pad_corpus(corpus, multiple)
    assert sc.n_padded == padded.n_tokens
    assert sc.shard_len % multiple == 0
    flat_w = sc.word_ids.reshape(-1)
    flat_d = sc.doc_ids.reshape(-1)
    flat_m = sc.mask.reshape(-1)
    sel = flat_m > 0
    assert np.array_equal(flat_w[sel], corpus.word_ids)
    assert np.array_equal(flat_d[sel], corpus.doc_ids)
    assert int(sc.real_per_shard.sum()) == corpus.n_tokens


@settings(max_examples=40, deadline=None)
@given(docs=_docs_strategy(), n_shards=st.integers(min_value=1, max_value=6))
def test_shard_stream_word_runs_match_slices(docs, n_shards):
    """Per-shard word-run metadata (first/last word, word_offsets CSR)
    and the inverted-index slice agree with the shard's real tokens."""
    from repro.lda.corpus import from_documents
    corpus = from_documents([np.asarray(d, np.int64) for d in docs], 30)
    sc = shard_stream(corpus, n_shards)
    for s in range(sc.n_shards):
        real = int(sc.real_per_shard[s])
        w = sc.word_ids[s, :real]
        counts = np.diff(sc.word_offsets[s])
        assert np.array_equal(counts,
                              np.bincount(w, minlength=sc.n_words))
        if real:
            assert sc.first_word[s] == w.min() == w[0]
            assert sc.last_word[s] == w.max() == w[-1]
        else:
            assert sc.last_word[s] < sc.first_word[s]   # empty sentinel
        # inverted index: covers each real slot once, grouped by doc
        idx = sc.inv_token_idx[s, :real]
        assert np.array_equal(np.sort(idx), np.arange(real))
        offs = sc.inv_doc_offsets[s]
        docs_of = sc.doc_ids[s, :real]
        for d in range(sc.n_docs):
            assert np.all(docs_of[idx[offs[d]:offs[d + 1]]] == d)


def test_shard_stream_rejects_bad_shard_count(small_corpus):
    with pytest.raises(ValueError, match="n_shards"):
        shard_stream(small_corpus, 0)


# ---------------------------------------------------------------------------
# 2. streamed == resident, bit for bit (single host)
# ---------------------------------------------------------------------------

def _final_states(corpus, base_kw, stream_kw, n_iters=4):
    tr_r = LDATrainer(corpus, LDAConfig(**base_kw), _from_engine=True)
    pr = tr_r.fused_pipeline()
    fr = pr.from_lda_state(tr_r.init_state())
    fr, _, _ = pr.run_fused(fr, n_iters)
    ref = pr.to_lda_state(fr)
    tr_s = LDATrainer(corpus, LDAConfig(**base_kw, **stream_kw),
                      _from_engine=True)
    assert tr_s.residency == "streamed"
    ps = tr_s.fused_pipeline()
    ss = ps.from_lda_state(tr_s.init_state())
    ss, stats, n_surv = ps.run_fused(ss, n_iters)
    out = ps.to_lda_state(ss)
    return ref, out, stats, n_surv


def _assert_bitwise(corpus, ref, out):
    n = corpus.n_tokens
    assert np.array_equal(np.asarray(ref.topics)[:n],
                          np.asarray(out.topics)[:n])
    assert np.array_equal(np.asarray(ref.D), np.asarray(out.D))
    assert np.array_equal(np.asarray(ref.W), np.asarray(out.W))
    assert int(ref.iteration) == int(out.iteration)


@pytest.mark.parametrize("fmt,extra", [
    ("dense", {}),
    ("hybrid", {}),
    ("hybrid", {"tail_sampler": "sparse"}),
    ("dense", {"balance": "tiles"}),
    ("dense", {"impl": "pallas"}),
])
def test_streamed_equals_resident_single(small_corpus, fmt, extra):
    base = dict(n_topics=16, tile_size=512, format=fmt, **extra)
    stream = dict(corpus_residency="streamed", stream_shards=4)
    ref, out, stats, n_surv = _final_states(small_corpus, base, stream)
    _assert_bitwise(small_corpus, ref, out)
    assert np.asarray(stats.frac_skipped).shape == (4,)
    assert np.asarray(n_surv).shape == (4,)
    assert (np.asarray(n_surv) > 0).all()


def test_stream_shard_count_is_a_pure_perf_knob(small_corpus):
    """Any shard count produces identical bits (like survivor capacity)."""
    outs = []
    for shards in (2, 3, 7):
        tr = LDATrainer(small_corpus, LDAConfig(
            n_topics=16, tile_size=512, corpus_residency="streamed",
            stream_shards=shards), _from_engine=True)
        pipe = tr.fused_pipeline()
        ss, _, _ = pipe.run_fused(pipe.from_lda_state(tr.init_state()), 3)
        outs.append(np.asarray(pipe.to_lda_state(ss).topics)
                    [:small_corpus.n_tokens])
    assert np.array_equal(outs[0], outs[1])
    assert np.array_equal(outs[1], outs[2])


# ---------------------------------------------------------------------------
# 3. mid-epoch checkpoints
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("fmt", ["dense", "hybrid"])
def test_mid_epoch_checkpoint_restores_bitwise(small_corpus, fmt, tmp_path):
    cfg = LDAConfig(n_topics=16, tile_size=512, format=fmt,
                    corpus_residency="streamed", stream_shards=4)
    tr = LDATrainer(small_corpus, cfg, _from_engine=True)
    pipe = tr.fused_pipeline()
    ss = pipe.from_lda_state(tr.init_state())
    ss, _, _ = pipe.run_fused(ss, 1)
    ref_ss = pipe.from_lda_state(tr.init_state())
    ref_ss, _, _ = pipe.run_fused(ref_ss, 3)           # uninterrupted
    ref = pipe.to_lda_state(ref_ss)

    # interrupt epoch 2 after 2 of 4 shards; round-trip through npz
    ss = pipe.run_shards(ss, 2)
    assert ss.cursor == 2
    payload = pipe.stream_payload(ss)
    assert int(payload["stream_cursor"]) == 2
    from repro.checkpoint import CheckpointManager
    mgr = CheckpointManager(str(tmp_path))
    mgr.save(int(ss.iteration), payload)
    restored = mgr.restore_latest()

    tr2 = LDATrainer(small_corpus, cfg, _from_engine=True)
    p2 = tr2.fused_pipeline()
    s2 = p2.state_from_stream_payload(restored)
    assert s2.cursor == 2
    s2, _, _ = p2.run_fused(s2, 2)   # finish epoch 2 + epoch 3
    _assert_bitwise(small_corpus, ref, p2.to_lda_state(s2))


def test_boundary_payload_is_canonical(small_corpus):
    """Epoch-boundary stream payloads carry no stream_* keys, so they
    interchange with every other backend's canonical checkpoints."""
    cfg = LDAConfig(n_topics=16, tile_size=512,
                    corpus_residency="streamed", stream_shards=3)
    tr = LDATrainer(small_corpus, cfg, _from_engine=True)
    pipe = tr.fused_pipeline()
    ss, _, _ = pipe.run_fused(pipe.from_lda_state(tr.init_state()), 2)
    payload = pipe.stream_payload(ss)
    assert sorted(payload) == ["iteration", "key", "topics_global"]
    # and a resident trainer restores it (through the engine's padding)
    tr_r = LDATrainer(small_corpus, LDAConfig(n_topics=16, tile_size=512),
                      _from_engine=True)
    padded = np.zeros(tr_r.word_ids.shape, np.int32)
    padded[:small_corpus.n_tokens] = payload["topics_global"]
    state = tr_r.state_from_payload({"topics": padded,
                                     "key": payload["key"],
                                     "iteration": payload["iteration"]})
    _assert_bitwise(small_corpus, pipe.to_lda_state(ss), state)


def test_mid_epoch_payload_rejected_by_resident_trainer(small_corpus):
    tr = LDATrainer(small_corpus, LDAConfig(n_topics=16, tile_size=512),
                    _from_engine=True)
    with pytest.raises(ValueError, match="mid-epoch"):
        tr.state_from_payload({
            "topics": np.zeros(tr.word_ids.shape, np.int32),
            "key": np.zeros(2, np.uint32), "iteration": 1,
            "stream_cursor": 2,
            "stream_done_topics": np.zeros(8, np.int32)})


def test_mid_epoch_payload_rejected_by_distributed_engine(small_corpus):
    """The engine's distributed backend must NOT silently strip the
    stream_* keys: a mid-epoch restore there would re-sample the done
    shards and bit-diverge without an error."""
    from repro.lda.api import LDAEngine
    from repro.runtime.compat import make_mesh
    payload = {"topics_global": np.zeros(small_corpus.n_tokens, np.int32),
               "key": np.zeros(2, np.uint32), "iteration": 1,
               "stream_cursor": np.int64(2),
               "stream_done_topics": np.zeros(8, np.int32)}
    eng = LDAEngine(small_corpus, LDAConfig(n_topics=16, tile_size=512),
                    backend="distributed",
                    mesh=make_mesh((1, 1), ("data", "model")),
                    pad_multiple=256)
    with pytest.raises(ValueError, match="mid-epoch"):
        eng.restore(payload)


def test_to_lda_state_requires_epoch_boundary(small_corpus):
    cfg = LDAConfig(n_topics=16, tile_size=512,
                    corpus_residency="streamed", stream_shards=4)
    tr = LDATrainer(small_corpus, cfg, _from_engine=True)
    pipe = tr.fused_pipeline()
    ss = pipe.run_shards(pipe.from_lda_state(tr.init_state()), 1)
    with pytest.raises(ValueError, match="epoch boundary"):
        pipe.to_lda_state(ss)


# ---------------------------------------------------------------------------
# 4. the engine surface
# ---------------------------------------------------------------------------

def test_engine_streamed_matches_resident(small_corpus):
    """LDAEngine(corpus_residency='streamed') fits to the same canonical
    payload as the resident engine, and their checkpoints interchange."""
    from repro.lda.api import LDAEngine
    kw = dict(n_topics=16, tile_size=512, eval_every=5)
    eng_r = LDAEngine(small_corpus, LDAConfig(**kw), backend="single")
    eng_s = LDAEngine(small_corpus, LDAConfig(
        corpus_residency="streamed", stream_shards=4, **kw),
        backend="single")
    hist_r = eng_r.fit(6)
    hist_s = eng_s.fit(6)
    pay_r, pay_s = eng_r.host_payload(), eng_s.host_payload()
    assert np.array_equal(pay_r["topics_global"], pay_s["topics_global"])
    assert hist_r["iteration"] == hist_s["iteration"]
    # streamed engine restores the resident engine's checkpoint
    eng_s2 = LDAEngine(small_corpus, LDAConfig(
        corpus_residency="streamed", stream_shards=4, **kw),
        backend="single").restore(pay_r)
    assert eng_s2.iteration == eng_r.iteration
    eng_s2.fit(2)
    eng_r.fit(2)
    assert np.array_equal(eng_r.host_payload()["topics_global"],
                          eng_s2.host_payload()["topics_global"])


def test_engine_mid_epoch_save_restore(small_corpus, tmp_path):
    """engine.restore() accepts a mid-epoch payload and fit() continues
    it bit-identically (the first epoch finishes the open one)."""
    from repro.lda.api import LDAEngine
    kw = dict(n_topics=16, tile_size=512, corpus_residency="streamed",
              stream_shards=4)
    eng = LDAEngine(small_corpus, LDAConfig(**kw), backend="single")
    eng.fit(3)
    ref = eng.host_payload()

    eng2 = LDAEngine(small_corpus, LDAConfig(**kw), backend="single")
    eng2.fit(1)
    # advance 2 shards mid-epoch through the pipeline surface
    pipe = eng2.trainer.fused_pipeline()
    ss = pipe.from_lda_state(eng2.state)
    ss = pipe.run_shards(ss, 2)
    eng2._state = ss
    mid = eng2.host_payload()              # canonical + stream_* keys
    assert int(mid["stream_cursor"]) == 2

    eng3 = LDAEngine(small_corpus, LDAConfig(**kw),
                     backend="single").restore(mid)
    eng3.fit(2)                            # finish epoch 2 + epoch 3
    out = eng3.host_payload()
    assert np.array_equal(ref["topics_global"], out["topics_global"])
    assert sorted(out) == ["iteration", "key", "topics_global"]


def test_engine_auto_residency_by_budget(small_corpus):
    """'auto' streams iff 16B x padded tokens exceeds the budget."""
    from repro.lda.api import LDAEngine
    padded_n = -(-small_corpus.n_tokens // 512) * 512
    tokens_bytes = STREAM_BYTES_PER_TOKEN * padded_n
    eng_small = LDAEngine(small_corpus, LDAConfig(
        n_topics=16, tile_size=512, corpus_residency="auto",
        device_budget_bytes=tokens_bytes // 2), backend="single")
    assert eng_small.trainer.residency == "streamed"
    eng_big = LDAEngine(small_corpus, LDAConfig(
        n_topics=16, tile_size=512, corpus_residency="auto",
        device_budget_bytes=tokens_bytes * 10), backend="single")
    assert eng_big.trainer.residency == "full"


# ---------------------------------------------------------------------------
# 5. residency/shard planning + config validation
# ---------------------------------------------------------------------------

def test_plan_stream_shards_window_math():
    # 2 * 20B * N/S must fit budget/4: N=1e6, budget=64MB -> S = 4 (floor)
    assert plan_stream_shards(10 ** 6, 64 << 20) == 4
    # tight budget forces more shards: window = 1MB -> S = ceil(40e6/1e6)
    assert plan_stream_shards(10 ** 6, 4 << 20) == \
        -(-2 * 20 * 10 ** 6 // (1 << 20))
    # never more shards than pad multiples
    assert plan_stream_shards(4096, 1, multiple=1024) == 4
    assert plan_stream_shards(0, None) == 1


def test_resolve_residency_modes():
    cfg_full = LDAConfig(n_topics=8)
    assert resolve_residency(cfg_full, 10 ** 9) == ("full", 1)
    cfg_s = LDAConfig(n_topics=8, corpus_residency="streamed",
                      stream_shards=6)
    assert resolve_residency(cfg_s, 1000) == ("streamed", 6)
    # auto with no budget signal on CPU: stays resident
    cfg_auto = LDAConfig(n_topics=8, corpus_residency="auto")
    assert resolve_residency(cfg_auto, 10 ** 9)[0] in ("full", "streamed")


def test_config_rejects_bad_streaming_knobs():
    with pytest.raises(ValueError, match="corpus_residency"):
        LDAConfig(n_topics=8, corpus_residency="paged")
    with pytest.raises(ValueError, match="stream_shards"):
        LDAConfig(n_topics=8, stream_shards=1)
    with pytest.raises(ValueError, match="device_budget_bytes"):
        LDAConfig(n_topics=8, device_budget_bytes=0)


# ---------------------------------------------------------------------------
# 6. distributed streaming (single real device; forged meshes are slow)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("fmt, balance", [("dense", "tiles"),
                                          ("hybrid", "none")],
                         ids=["dense", "hybrid"])
def test_streamed_equals_resident_distributed(small_corpus, fmt, balance):
    """Streamed == resident where both run the per-shard sweep and its
    draw: the dense leg under balance='tiles', since the resident dense
    data-parallel path draws the single backend's topics instead."""
    from repro.lda.distributed import DistLDATrainer, DistStreamState
    from repro.runtime.compat import make_mesh
    mesh = make_mesh((1, 1), ("data", "model"))
    kw = dict(n_topics=16, tile_size=512, format=fmt, balance=balance)
    tr_r = DistLDATrainer(small_corpus, LDAConfig(**kw), mesh,
                          pad_multiple=256, _from_engine=True)
    s_r, _ = tr_r.run_fused(tr_r.init_state(), 4)
    tr_s = DistLDATrainer(small_corpus, LDAConfig(
        corpus_residency="streamed", stream_shards=3, **kw), mesh,
        pad_multiple=256, _from_engine=True)
    state = tr_s.init_state()
    assert isinstance(state, DistStreamState)
    with pytest.raises(ValueError, match="epochs"):
        tr_s.step(state)
    s_s, stats = tr_s.run_fused(state, 4)
    assert np.asarray(stats.frac_skipped).shape == (4,)
    pay_r, pay_s = tr_r.host_payload(s_r), tr_s.host_payload(s_s)
    assert np.array_equal(pay_r["topics_global"], pay_s["topics_global"])
    D_r, W_r = tr_r.gather_global(s_r)
    D_s, W_s = tr_s.gather_global(s_s)
    assert np.array_equal(D_r, D_s)
    assert np.array_equal(W_r, W_s)
    # checkpoints interchange: streamed payload restores resident & back
    # (pad-slot topics are inert derived state — compare real slots)
    sel = tr_r.sc.mask > 0
    s_r2 = tr_r.state_from_payload(pay_s)
    assert np.array_equal(np.asarray(s_r2.topics)[sel],
                          np.asarray(s_r.topics)[sel])
    s_s2 = tr_s.state_from_payload(pay_r)
    n_loc = tr_s.stream.n_loc
    assert np.array_equal(s_s2.host_topics[:, :n_loc][sel],
                          s_s.host_topics[:, :n_loc][sel])


@pytest.mark.slow
def test_streamed_distributed_forged_devices():
    """Streamed == resident over a real multi-device mesh (8 forged CPU
    devices), including balance='tiles' dissection and model parallelism."""
    import subprocess, sys, textwrap
    code = """
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import sys; sys.path.insert(0, "src")
    import numpy as np, jax
    from repro.lda.corpus import synthetic_lda_corpus, relabel_by_frequency
    from repro.lda.model import LDAConfig
    from repro.lda.distributed import DistLDATrainer
    from repro.runtime.compat import make_mesh
    corpus = synthetic_lda_corpus(0, n_docs=80, n_words=100, n_topics=8,
                                  mean_doc_len=50)
    corpus, _ = relabel_by_frequency(corpus)
    for shape, fmt, bal in (((4, 2), "dense", "none"),
                            ((4, 1), "dense", "tiles"),
                            ((8, 1), "hybrid", "none")):
        mesh = make_mesh(shape, ("data", "model"))
        kw = dict(n_topics=16, tile_size=512, format=fmt, balance=bal)
        tr_r = DistLDATrainer(corpus, LDAConfig(**kw), mesh,
                              pad_multiple=256, _from_engine=True)
        s_r, _ = tr_r.run_fused(tr_r.init_state(), 4)
        tr_s = DistLDATrainer(corpus, LDAConfig(
            corpus_residency="streamed", stream_shards=3, **kw), mesh,
            pad_multiple=256, _from_engine=True)
        s_s, _ = tr_s.run_fused(tr_s.init_state(), 4)
        assert np.array_equal(tr_r.host_payload(s_r)["topics_global"],
                              tr_s.host_payload(s_s)["topics_global"]), \\
            (shape, fmt, bal)
        D_r, W_r = tr_r.gather_global(s_r)
        D_s, W_s = tr_s.gather_global(s_s)
        assert np.array_equal(D_r, D_s) and np.array_equal(W_r, W_s)
    print("OK")
    """
    proc = subprocess.run(
        [sys.executable, "-c", textwrap.dedent(code)],
        capture_output=True, text=True, timeout=900, cwd=".")
    assert proc.returncode == 0, proc.stderr[-4000:]
    assert "OK" in proc.stdout


# ---------------------------------------------------------------------------
# 7. measured memory accounting
# ---------------------------------------------------------------------------

# ---------------------------------------------------------------------------
# 8. disk-native training (corpus_residency="disk", DESIGN.md SS14)
# ---------------------------------------------------------------------------

def _disk_store(corpus, tmp_path, n_shards=4, multiple=512):
    return shard_stream(corpus, n_shards, multiple=multiple).to_store(
        str(tmp_path / "store"))


def _disk_engine(store, tmp_path=None, **kw):
    from repro.lda.api import LDAEngine
    cfg = LDAConfig(corpus_residency="disk", corpus_path=store.path, **kw)
    ck = {} if tmp_path is None else \
        {"checkpoint_dir": str(tmp_path / "ck")}
    return LDAEngine(None, cfg, backend="single", **ck)


@pytest.mark.parametrize("fmt,extra", [
    ("dense", {}),
    ("hybrid", {}),
    ("hybrid", {"tail_sampler": "sparse"}),
    ("dense", {"balance": "tiles"}),
    ("dense", {"impl": "pallas"}),
])
def test_disk_equals_streamed_equals_resident(small_corpus, fmt, extra,
                                              tmp_path):
    """The full residency ladder is bitwise ONE training run: resident ==
    streamed == disk-native (corpus read from shard files, W paged per
    shard) on topics, key, and the exact LLPT history."""
    from repro.lda.api import LDAEngine
    kw = dict(n_topics=16, tile_size=512, eval_every=2, format=fmt, **extra)
    eng_r = LDAEngine(small_corpus, LDAConfig(**kw), backend="single")
    hist_r = eng_r.fit(4)
    store = _disk_store(eng_r.corpus, tmp_path)
    eng_s = LDAEngine(small_corpus, LDAConfig(
        corpus_residency="streamed", stream_shards=4, **kw),
        backend="single")
    hist_s = eng_s.fit(4)
    eng_d = _disk_engine(store, **kw)
    assert eng_d.trainer.residency == "disk"
    assert eng_d.trainer.fused_pipeline().paged
    hist_d = eng_d.fit(4)
    pay_r, pay_s, pay_d = (eng_r.host_payload(), eng_s.host_payload(),
                           eng_d.host_payload())
    assert np.array_equal(pay_r["topics_global"], pay_s["topics_global"])
    assert np.array_equal(pay_r["topics_global"], pay_d["topics_global"])
    assert np.array_equal(pay_r["key"], pay_d["key"])
    assert hist_r["llpt"] == hist_s["llpt"] == hist_d["llpt"]


def test_disk_eval_equals_resident_eval_exactly(small_corpus, tmp_path):
    """Paged shard-fold LLPT == resident LLPT bitwise: per-token values
    through the one same compiled reduce (core/llpt.py split)."""
    from repro.lda.api import LDAEngine
    kw = dict(n_topics=16, tile_size=512, eval_every=5)
    eng_r = LDAEngine(small_corpus, LDAConfig(**kw), backend="single")
    eng_r.fit(3)
    store = _disk_store(eng_r.corpus, tmp_path)
    eng_d = _disk_engine(store, **kw)
    eng_d.fit(3)
    assert eng_r.score() == eng_d.score()


def test_disk_checkpoints_interchange_with_resident(small_corpus, tmp_path):
    """A disk engine restores a resident engine's canonical checkpoint
    and continues bitwise, and vice versa."""
    from repro.lda.api import LDAEngine
    kw = dict(n_topics=16, tile_size=512, eval_every=5)
    eng_r = LDAEngine(small_corpus, LDAConfig(**kw), backend="single")
    eng_r.fit(2)
    store = _disk_store(eng_r.corpus, tmp_path)
    eng_d = _disk_engine(store, **kw).restore(eng_r.host_payload())
    eng_d.fit(2)
    eng_r.fit(2)
    pay_r, pay_d = eng_r.host_payload(), eng_d.host_payload()
    assert np.array_equal(pay_r["topics_global"], pay_d["topics_global"])
    # and back: the resident engine restores the disk engine's payload
    eng_r2 = LDAEngine(small_corpus, LDAConfig(**kw),
                       backend="single").restore(pay_d)
    assert eng_r2.iteration == eng_d.iteration
    assert eng_r2.score() == eng_d.score()


def test_disk_mid_epoch_checkpoint_resumes_bitwise(small_corpus, tmp_path):
    """A mid-epoch disk payload (manifest-relative stream cursor) restores
    into a FRESH engine and finishes bit-identically."""
    from repro.lda.api import LDAEngine
    kw = dict(n_topics=16, tile_size=512, eval_every=5)
    eng_r = LDAEngine(small_corpus, LDAConfig(**kw), backend="single")
    eng_r.fit(3)
    ref = eng_r.host_payload()
    store = _disk_store(eng_r.corpus, tmp_path)

    eng_d = _disk_engine(store, **kw)
    eng_d.fit(1)
    pipe = eng_d.trainer.fused_pipeline()
    ss = pipe.run_shards(pipe.from_lda_state(eng_d.state), 2)
    eng_d._state = ss
    mid = eng_d.host_payload()
    assert int(mid["stream_cursor"]) == 2
    assert int(mid["stream_n_shards"]) == store.n_shards

    eng_d2 = _disk_engine(store, **kw).restore(mid)
    eng_d2.fit(2)               # finish epoch 2 + epoch 3
    assert np.array_equal(ref["topics_global"],
                          eng_d2.host_payload()["topics_global"])


def test_mid_epoch_payload_rejects_shard_grid_mismatch(small_corpus,
                                                      tmp_path):
    """A mid-epoch cursor is only meaningful on the shard grid it was
    saved against: restoring it into a store with a different n_shards
    must fail loudly, not resample the wrong shards."""
    from repro.lda.api import LDAEngine
    kw = dict(n_topics=16, tile_size=512, eval_every=5)
    eng_r = LDAEngine(small_corpus, LDAConfig(**kw), backend="single")
    eng_r.fit(1)
    store4 = _disk_store(eng_r.corpus, tmp_path)
    eng_d = _disk_engine(store4, **kw)
    eng_d.fit(1)
    pipe = eng_d.trainer.fused_pipeline()
    ss = pipe.run_shards(pipe.from_lda_state(eng_d.state), 2)
    eng_d._state = ss
    mid = eng_d.host_payload()
    store2 = shard_stream(eng_r.corpus, 2, multiple=512).to_store(
        str(tmp_path / "store2"))
    eng_d2 = _disk_engine(store2, **kw)
    with pytest.raises(ValueError, match="shard grid"):
        eng_d2.restore(mid)


def test_disk_config_validation(tmp_path):
    with pytest.raises(ValueError, match="corpus_path"):
        LDAConfig(n_topics=8, corpus_residency="disk")
    with pytest.raises(ValueError, match="corpus_path"):
        LDAConfig(n_topics=8, corpus_path="/somewhere")
    with pytest.raises(ValueError, match="stream_shards"):
        LDAConfig(n_topics=8, corpus_residency="disk",
                  corpus_path="/somewhere", stream_shards=4)


def test_disk_engine_guards(small_corpus, tmp_path):
    from repro.lda.api import LDAEngine
    store = _disk_store(small_corpus, tmp_path)
    cfg = LDAConfig(n_topics=16, tile_size=512, corpus_residency="disk",
                    corpus_path=store.path)
    # a resident corpus alongside a disk config would silently diverge
    with pytest.raises(ValueError, match="corpus=None"):
        LDAEngine(small_corpus, cfg, backend="single")
    # no corpus and no store path is no corpus at all
    with pytest.raises(ValueError, match="disk"):
        LDAEngine(None, LDAConfig(n_topics=16), backend="single")
    # disk is single-backend: the paged pipeline owns the device schedule
    with pytest.raises(ValueError, match="single"):
        LDAEngine(None, cfg, backend="distributed")
    # the stepwise oracle path needs resident tokens
    eng = LDAEngine(None, cfg, backend="single")
    eng.fit(1)
    with pytest.raises(ValueError, match="resident"):
        eng.trainer.step(eng.state)


def test_disk_pages_w_per_shard(small_corpus, tmp_path):
    """The paged pipeline's device window holds a W ROW BLOCK, not the
    full matrix: page_rows is the max word-run span, and the epoch's
    device-byte accounting reflects the paged window."""
    store = _disk_store(small_corpus, tmp_path, n_shards=8)
    cfg = LDAConfig(n_topics=16, tile_size=512, corpus_residency="disk",
                    corpus_path=store.path)
    tr = LDATrainer(None, cfg, _from_engine=True)
    pipe = tr.fused_pipeline()
    assert pipe.paged
    spans = np.maximum(
        store.last_word.astype(np.int64) - store.first_word + 1, 1)
    assert pipe._page_rows == min(max(int(spans.max()), 1), store.n_words)
    assert pipe._page_rows < store.n_words      # a real window, not all of W
    ss, _, _ = pipe.run_fused(pipe.from_lda_state(tr.init_state()), 1)
    assert pipe.last_epoch_device_bytes > 0
    # serving view at the boundary is the exact at-rest W
    W, cursor, n_sh = pipe.serving_counts(ss)
    assert cursor == 0 and n_sh == store.n_shards
    assert np.array_equal(W, np.asarray(pipe.to_lda_state(ss).W))


@pytest.mark.slow
def test_disk_equals_resident_forged_devices(tmp_path):
    """disk == resident bitwise with 8 forged CPU devices visible: the
    single-backend paged pipeline must not be perturbed by a multi-device
    runtime (and engine backend='auto' must route disk to single)."""
    import subprocess, sys, textwrap
    code = f"""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import sys; sys.path.insert(0, "src")
    import numpy as np, jax
    from repro.lda.corpus import (synthetic_lda_corpus, relabel_by_frequency,
                                  shard_stream)
    from repro.lda.model import LDAConfig
    from repro.lda.api import LDAEngine
    corpus = synthetic_lda_corpus(0, n_docs=80, n_words=100, n_topics=8,
                                  mean_doc_len=50)
    corpus, _ = relabel_by_frequency(corpus)
    store = shard_stream(corpus, 3, multiple=512).to_store(
        {str(tmp_path / "store8")!r})
    for fmt in ("dense", "hybrid"):
        kw = dict(n_topics=16, tile_size=512, eval_every=5, format=fmt)
        eng_r = LDAEngine(corpus, LDAConfig(**kw), backend="single")
        eng_r.fit(4)
        eng_d = LDAEngine(None, LDAConfig(
            corpus_residency="disk", corpus_path=store.path, **kw))
        assert eng_d.backend_name == "single"       # auto routes to single
        eng_d.fit(4)
        pr, pd = eng_r.host_payload(), eng_d.host_payload()
        assert np.array_equal(pr["topics_global"], pd["topics_global"]), fmt
        assert np.array_equal(pr["key"], pd["key"]), fmt
        assert eng_r.score() == eng_d.score(), fmt
    print("OK")
    """
    proc = subprocess.run(
        [sys.executable, "-c", textwrap.dedent(code)],
        capture_output=True, text=True, timeout=900, cwd=".")
    assert proc.returncode == 0, proc.stderr[-4000:]
    assert "OK" in proc.stdout


def test_streamed_device_bytes_below_resident(small_corpus):
    """The streaming window accounting: resident token+state bytes vs
    the streamed steady state (counts + epoch arrays + two shard
    windows). On this small corpus the absolute win is modest; the
    token-side win is what this checks."""
    cfg = LDAConfig(n_topics=16, tile_size=512,
                    corpus_residency="streamed", stream_shards=8)
    tr = LDATrainer(small_corpus, cfg, _from_engine=True)
    pipe = tr.fused_pipeline()
    ss, _, _ = pipe.run_fused(pipe.from_lda_state(tr.init_state()), 1)
    assert pipe.last_epoch_device_bytes > 0
    resident_token_bytes = pipe.stream.token_bytes_resident()
    streamed_token_bytes = pipe.stream.token_bytes_streamed()
    assert streamed_token_bytes < resident_token_bytes
    assert streamed_token_bytes == 2 * 20 * pipe.stream.shard_len


def test_disk_device_bytes_below_resident(tmp_path):
    """Where W dominates (V = 3,000 words, 2,489 tokens), the paged
    pipeline's measured steady-state device bytes stay under 0.45× the
    resident path's token arrays plus fused state, and its W page window
    is a strict slice of the vocabulary."""
    from repro.lda.corpus import relabel_by_frequency, zipf_corpus
    c, _ = relabel_by_frequency(zipf_corpus(0, n_docs=60, n_words=3000,
                                            exponent=1.25, mean_doc_len=40))
    store = shard_stream(c, 8, multiple=64).to_store(str(tmp_path / "s"))
    tr_r = LDATrainer(c, LDAConfig(n_topics=16, tile_size=64),
                      _from_engine=True)
    pipe_r = tr_r.fused_pipeline()
    fr, _, _ = pipe_r.run_fused(pipe_r.from_lda_state(tr_r.init_state()), 1)
    resident = sum(int(a.nbytes) for a in jax.tree.leaves(
        (tr_r.word_ids, tr_r.doc_ids, tr_r.mask, tuple(fr))))
    tr_d = LDATrainer(None, LDAConfig(n_topics=16, tile_size=64,
                                      corpus_residency="disk",
                                      corpus_path=store.path),
                      _from_engine=True)
    pipe_d = tr_d.fused_pipeline()
    pipe_d.run_fused(pipe_d.from_lda_state(tr_d.init_state()), 1)
    assert pipe_d._page_rows < store.n_words
    assert 0 < pipe_d.last_epoch_device_bytes <= 0.45 * resident, (
        pipe_d.last_epoch_device_bytes, resident)
