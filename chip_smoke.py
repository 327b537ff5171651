#!/usr/bin/env python3
"""Smoke run of EZLDA on a TPU at the NYTimes deployment's width.

Drives the main path once, through the entry points a user calls, in the
one process that holds the chip:

  1. device    the backend must be ``tpu`` (there is no CPU fallback);
  2. kernels   the compiled Pallas kernels agree with their pure-jnp
               oracles on a small input at K = 1,000;
  3. corpus    a NYTimes-shaped corpus from a fixed seed: V = 101,636
               words, Zipf exponent 1.1, 299,752 documents of Poisson
               length, about 100 M tokens (``DOC_FRACTION`` cuts only the
               document count), relabelled by frequency;
  4. train     ``LDAEngine.fit`` for ``N_ITERS`` iterations at K = 1,000 in
               three configurations: the default ``LDAConfig``, the fused
               Pallas pipeline (``sample_fused``), and the hybrid state
               with the sparse tail sampler (``sample_sparse``). Each must
               conserve its counts and keep LLPT finite, and the Pallas
               configurations must run compiled Mosaic kernels;
  5. serve     ``engine.export()`` then ``FrozenLDAModel.transform`` on 64
               held-out documents: every theta row finite and summing to 1.

``--four-chips`` runs only the data-parallel path on a (4, 1) mesh, and a
one-chip run of the same corpus and seed to compare its LLPT with.

Usage:  python chip_smoke.py [--four-chips]

The last line of standard output is one JSON object,
``{"ok": true, "device": {"platform", "kind", "count"}}``, printed only
when every phase passed. Times printed on the way are what this run saw,
not benchmark metrics.
"""

from __future__ import annotations

import argparse
import gc
import json
import os
import sys
import time

import numpy as np

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))

# NYTimes (paper §VI, benchmarks/_common.DATASETS) at K = 1,000
N_WORDS = 101_636
N_DOCS = 299_752
N_TOKENS = 100_000_000
ZIPF = 1.1
N_TOPICS = 1_000
# Share of the 299,752 documents this run keeps. Time forces the cut: on
# one v5e the hybrid configuration alone compiles for ~4.5 min from cold
# and takes ~23 s per iteration already at 0.05, and the whole run must
# end within 20 minutes.
DOC_FRACTION = 0.25
N_ITERS = 3
N_HELDOUT = 64
SEED = 0
# the kernel check's input: tokens, tile word window, hybrid D slots
KERNEL_TOKENS = 2_048
KERNEL_WINDOW = 256
KERNEL_SLOTS = 416
# |LLPT(4 chips) - LLPT(1 chip)| after N_ITERS iterations, bits per token.
# The two runs draw different random streams from one seed; over tens of
# millions of tokens their LLPTs differ by sampling noise far below this
# bound.
LLPT_TOL = 0.01


def say(*parts) -> None:
    print(*parts, flush=True)


class Fail(RuntimeError):
    """A phase's check did not hold."""


def check(ok: bool, what: str) -> None:
    if not ok:
        raise Fail(what)


# -- 1. device ---------------------------------------------------------------

def device_phase(want: int) -> dict:
    import jax
    backend = jax.default_backend()
    devs = jax.devices()
    say(f"[device] backend={backend} count={len(devs)} "
        + " ".join(f"{d.platform}:{d.device_kind}" for d in devs))
    if backend != "tpu":
        raise Fail(f"no TPU: JAX's default backend is {backend!r}, and this "
                   "smoke run never falls back to another device")
    check(len(devs) >= want, f"needs {want} TPU chips, found {len(devs)}")
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": want}


# -- 2. kernels --------------------------------------------------------------

def kernel_phase() -> None:
    """The compiled kernels against their pure-jnp oracles
    (``repro.kernels.ref``) on a small input at K = ``N_TOPICS``, to the
    tolerances of ``tests/test_kernels.py``: masses to float rounding,
    topics equal but for a measure-zero set at CDF boundaries."""
    import jax.numpy as jnp
    from repro.core.sparse import pack_pairs
    from repro.kernels import ref
    from repro.kernels.histogram import histogram
    from repro.kernels.sample_fused import sample_fused, sample_fused_tiled
    from repro.kernels.sample_sparse import sample_sparse
    t0 = time.perf_counter()
    rng = np.random.default_rng(SEED)
    n, k = KERNEL_TOKENS, N_TOPICS
    alpha = 50.0 / k
    u = jnp.asarray(rng.random(n, dtype=np.float32))
    d = jnp.asarray(rng.integers(0, 50, (n, k))
                    * (rng.random((n, k)) < 0.1), jnp.int32)
    w_hat = jnp.asarray(rng.random((2 * KERNEL_WINDOW, k), np.float32) * 0.01)
    words = jnp.asarray(rng.integers(0, KERNEL_WINDOW, n), jnp.int32) \
        + KERNEL_WINDOW // 2
    w = w_hat[words]

    def mismatch(a, b) -> float:
        return float(np.mean(np.asarray(a) != np.asarray(b)))

    def close(a, b, rtol, atol=0.0) -> bool:
        return bool(np.allclose(np.asarray(a), np.asarray(b), rtol=rtol,
                                atol=atol))

    got = sample_fused(u, d, w, alpha=alpha)
    want = ref.sample_fused_ref(u, d, w, alpha=alpha)
    tiled = sample_fused_tiled(u, d, w_hat, words, KERNEL_WINDOW // 2,
                               alpha=alpha, win_words=KERNEL_WINDOW)
    fused_ok = (close(got[1], want[1], 1e-5)
                and close(got[2], want[2], 1e-4, 1e-6)
                and close(got[3], want[3], 1e-4, 1e-6))
    fused_miss = mismatch(got[0], want[0])
    tiled_miss = mismatch(tiled[0], got[0])

    slots = min(KERNEL_SLOTS, k)
    idx = np.argsort(rng.random((n, k)), axis=1)[:, :slots].astype(np.int32)
    val = (rng.integers(1, 30, (n, slots))
           * (np.arange(slots) < rng.integers(0, slots + 1, n)[:, None]))
    w_row = rng.random(k).astype(np.float32) * 0.01
    sp_args = (jnp.asarray(w_row[idx]),
               jnp.asarray(rng.integers(0, k, n), jnp.int32),
               jnp.asarray(rng.random(n, dtype=np.float32) * 0.02),
               jnp.asarray(rng.integers(0, 20, n), jnp.float32),
               jnp.asarray(rng.random(n, dtype=np.float32) * 0.05))
    sp = sample_sparse(u, pack_pairs(jnp.asarray(idx), jnp.asarray(val)),
                       *sp_args, alpha=alpha)
    sp_ref = ref.sample_sparse_ref(u, jnp.asarray(idx), jnp.asarray(val),
                                   *sp_args, alpha=alpha)
    sparse_ok = (close(sp[2], sp_ref[2], 1e-5, 1e-7)
                 and bool(np.array_equal(sp[1], sp_ref[1])))
    sparse_miss = mismatch(sp[0], sp_ref[0])

    rows = jnp.asarray(np.sort(rng.integers(0, 300, n)), jnp.int32)
    hist_args = (rows, jnp.asarray(rng.integers(0, k, n), jnp.int32),
                 jnp.asarray(rng.random(n) < 0.9, jnp.int32))
    hist_ok = bool(np.array_equal(
        histogram(*hist_args, n_rows=300, n_topics=k, tile_t=512,
                  rows_per_tile=64),
        ref.histogram_ref(*hist_args, n_rows=300, n_topics=k)))
    say(f"[kernels] tokens={n} K={k}: sample_fused masses_ok={fused_ok} "
        f"topic_mismatch={fused_miss}; sample_fused_tiled vs plain "
        f"topic_mismatch={tiled_miss}; sample_sparse ok={sparse_ok} "
        f"topic_mismatch={sparse_miss}; histogram exact={hist_ok} "
        f"seconds={time.perf_counter() - t0:.1f}")
    check(fused_ok and fused_miss < 2e-3, "sample_fused disagrees with its "
          "oracle")
    check(tiled_miss < 2e-3, "sample_fused_tiled disagrees with "
          "sample_fused")
    check(sparse_ok and sparse_miss < 2e-3, "sample_sparse disagrees with "
          "its oracle")
    check(hist_ok, "histogram disagrees with its oracle")


# -- 3. corpus ---------------------------------------------------------------

def zipf_docs(rng, n_docs: int, n_words: int, mean_len: float):
    """``n_docs`` documents of Poisson(``mean_len``) length whose words
    are i.i.d. Zipf(``ZIPF``) over ``n_words`` ranks: one vectorised draw
    for every token, split into per-document views."""
    lens = np.maximum(rng.poisson(mean_len, n_docs), 1)
    cdf = np.cumsum(np.arange(1, n_words + 1, dtype=np.float64) ** -ZIPF)
    words = np.searchsorted(cdf, rng.random(int(lens.sum())) * cdf[-1],
                            side="right")
    words = np.minimum(words, n_words - 1).astype(np.int32)
    return np.split(words, np.cumsum(lens)[:-1])


def corpus_phase(n_docs: int):
    from repro.lda.corpus import from_documents, relabel_by_frequency
    t0 = time.perf_counter()
    rng = np.random.default_rng(SEED)
    docs = zipf_docs(rng, n_docs, N_WORDS, N_TOKENS / N_DOCS)
    corpus, word_map = relabel_by_frequency(from_documents(docs, N_WORDS))
    heldout = zipf_docs(np.random.default_rng(SEED + 1), N_HELDOUT,
                        N_WORDS, N_TOKENS / N_DOCS)
    say(f"[corpus] V={corpus.n_words} docs={corpus.n_docs} "
        f"tokens={corpus.n_tokens} zipf={ZIPF} "
        f"host_seconds={time.perf_counter() - t0:.1f}")
    # device bytes of the dense resident state (ROADMAP R2's reckoning)
    tok = 16 * corpus.n_tokens
    w = 8 * corpus.n_words * N_TOPICS
    d = 4 * corpus.n_docs * N_TOPICS
    say(f"[corpus] device bytes: tokens={tok / 1e9:.2f}GB "
        f"W+What={w / 1e9:.2f}GB D={d / 1e9:.2f}GB "
        f"sum={(tok + w + d) / 1e9:.2f}GB")
    return corpus, word_map, heldout, tok + w + d


def mem_stat(dev, key: str = "bytes_in_use") -> int:
    return dev.memory_stats()[key]


def fits_phase(need: int, devices) -> None:
    for dev in devices:
        limit = dev.memory_stats()["bytes_limit"]
        check(need < limit, f"resident state {need} B does not fit "
                            f"{dev} ({limit} B)")
    say(f"[corpus] fits: per-chip limit "
        f"{devices[0].memory_stats()['bytes_limit'] / 1e9:.2f}GB")


# -- 4. train ----------------------------------------------------------------

def count_sums(engine) -> tuple[int, int]:
    """(D.sum(), W.sum()) of the engine's current counts."""
    import jax.numpy as jnp
    if engine.backend_name == "distributed":
        D, W = engine.trainer.gather_global(engine.state)
        return int(D.sum()), int(W.sum())
    return int(jnp.sum(engine.state.D)), int(jnp.sum(engine.state.W))


def kernel_evidence(engine) -> tuple[bool, int]:
    """(interpret flag the pipeline resolved, tpu_custom_call ops in the
    compiled program of its first scan). The program is compiled again
    from the same lowering, which the persistent cache answers."""
    pipe = engine.trainer.fused_pipeline()
    fn = next(iter(pipe._step_cache.values()))
    fs = pipe.from_lda_state(engine.state)
    text = fn.lower(fs, pipe._token_args()).compile().as_text()
    del fs
    return pipe._interpret, text.count("tpu_custom_call")


def train_phase(name: str, corpus, config, *,
                pallas: bool, **engine_kw):
    import jax
    from repro.lda.api import LDAEngine
    from repro.runtime import compiles
    t0 = time.perf_counter()
    c0 = compiles.seconds()         # lowering and XLA compilation
    engine = LDAEngine(corpus, config, **engine_kw)
    engine.fit(0)                                   # build the initial state
    llpt0 = engine.score()
    t_init = time.perf_counter() - t0
    c1 = compiles.seconds()
    t1 = time.perf_counter()
    hist = engine.fit(N_ITERS)
    t_fit = time.perf_counter() - t1
    c_fit = compiles.seconds() - c1
    t2 = time.perf_counter()
    llpt = engine.score()
    t_eval = time.perf_counter() - t2
    # fit() also scored once, after its first iteration
    steady = (t_fit - c_fit - t_eval) / N_ITERS
    d_sum, w_sum = count_sums(engine)
    line = {"config": name, "backend": engine.backend_name,
            "compile_s": round(c1 - c0 + c_fit, 1),
            "steady_s_per_iter": round(steady, 3),
            "init_s": round(t_init, 1), "fit_s": round(t_fit, 1),
            "conserved": d_sum == w_sum == corpus.n_tokens,
            "llpt_iter0": llpt0, "llpt_fit": hist["llpt"],
            f"llpt_iter{N_ITERS}": llpt,
            "peak_bytes": mem_stat(jax.devices()[0], "peak_bytes_in_use")}
    if pallas:
        t3 = time.perf_counter()
        interpret, n_calls = kernel_evidence(engine)
        line.update(interpret=interpret, tpu_custom_calls=n_calls,
                    evidence_s=round(time.perf_counter() - t3, 1))
    say(f"[train] {json.dumps(line)}")
    check(line["conserved"], f"{name}: counts not conserved "
          f"(D={d_sum} W={w_sum} tokens={corpus.n_tokens})")
    check(all(np.isfinite(v) for v in (llpt0, llpt, *hist["llpt"])),
          f"{name}: LLPT not finite")
    if pallas:
        check(line["interpret"] is False, f"{name}: kernels interpreted")
        check(line["tpu_custom_calls"] > 0,
              f"{name}: no tpu_custom_call in the compiled program")
    return engine, llpt


def release(*objs) -> None:
    import jax
    del objs
    gc.collect()
    jax.clear_caches()
    gc.collect()


# -- 5. serve ----------------------------------------------------------------

def serve_phase(engine, word_map, heldout) -> None:
    t0 = time.perf_counter()
    model = engine.export()
    docs = [word_map[d] for d in heldout]
    theta = model.transform(docs)
    ok = (theta.shape == (N_HELDOUT, N_TOPICS)
          and bool(np.all(np.isfinite(theta)))
          and bool(np.allclose(theta.sum(axis=1), 1.0, atol=1e-4)))
    say(f"[serve] docs={len(docs)} theta={theta.shape} "
        f"row_sum_max_err={float(np.abs(theta.sum(axis=1) - 1).max()):.2e} "
        f"seconds={time.perf_counter() - t0:.1f}")
    check(ok, "serving: theta rows are not finite distributions")


# -- the runs ----------------------------------------------------------------

def one_chip() -> dict:
    import jax
    from repro.lda.model import LDAConfig
    device = device_phase(1)
    kernel_phase()
    n_docs = round(N_DOCS * DOC_FRACTION)
    say(f"[corpus] document fraction {DOC_FRACTION} of {N_DOCS}")
    corpus, word_map, heldout, need = corpus_phase(n_docs)
    fits_phase(need, jax.devices()[:1])
    configs = (
        ("default", LDAConfig(n_topics=N_TOPICS), False),
        ("pallas_fused", LDAConfig(n_topics=N_TOPICS, impl="pallas",
                                   fused=True), True),
        ("hybrid_sparse", LDAConfig(n_topics=N_TOPICS, format="hybrid",
                                    impl="pallas", tail_sampler="sparse"),
         True),
    )
    for name, cfg, pallas in configs:
        engine, _ = train_phase(name, corpus, cfg, pallas=pallas,
                                backend="single")
        if name == "default":
            serve_phase(engine, word_map, heldout)
        release(engine)
        say(f"[train] {name}: bytes_in_use after release "
            f"{mem_stat(jax.devices()[0])}")
    return device


def four_chips() -> dict:
    import jax
    from repro.lda.model import LDAConfig
    from repro.runtime.compat import make_mesh
    device = device_phase(4)
    devs = jax.devices()[:4]
    n_docs = round(N_DOCS * DOC_FRACTION)
    say(f"[corpus] document fraction {DOC_FRACTION} of {N_DOCS}")
    corpus, _, _, need = corpus_phase(n_docs)
    fits_phase(need, devs[:1])
    cfg = LDAConfig(n_topics=N_TOPICS)
    mesh = make_mesh((4, 1), ("data", "model"), devices=devs)
    engine, llpt4 = train_phase("distributed_4x1", corpus, cfg,
                                pallas=False, backend="distributed",
                                mesh=mesh)
    holders = {s.device for s in engine.state.D.addressable_shards
               if s.data.size}
    in_use = [mem_stat(d) for d in devs]
    say(f"[four] bytes_in_use per chip {in_use}; D shards on "
        f"{len(holders)} chips")
    check(holders == set(devs) and min(in_use) > 0,
          "a chip holds no shard of the distributed state")
    release(engine)
    _, llpt1 = train_phase("single_chip", corpus, cfg, pallas=False,
                           backend="single")
    diff = abs(llpt4 - llpt1)
    say(f"[four] llpt 4 chips {llpt4:.6f} vs 1 chip {llpt1:.6f}: "
        f"|diff| {diff:.6f} (tolerance {LLPT_TOL})")
    check(diff <= LLPT_TOL, "4-chip and 1-chip LLPT disagree")
    return device


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the (4, 1) data-parallel path and its "
                         "one-chip comparison")
    args = ap.parse_args(argv)
    from repro.runtime.compile_cache import enable_compile_cache
    cache = enable_compile_cache()
    warm = os.path.isdir(cache) and bool(os.listdir(cache))
    say(f"[cache] {cache} ({'warm' if warm else 'cold'} at start)")
    t0 = time.perf_counter()
    try:
        device = four_chips() if args.four_chips else one_chip()
    except Fail as e:
        print(f"FAILED: {e}", file=sys.stderr, flush=True)
        return 1
    say(f"[total] seconds={time.perf_counter() - t0:.1f}")
    say(json.dumps({"ok": True, "device": device}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
