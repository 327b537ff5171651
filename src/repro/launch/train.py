"""The LDA command line: EZLDA training (sample→update→LLPT) through the
``repro.lda.api.LDAEngine`` front door — backend auto-selected by device
count, checkpoint/restart via --checkpoint-dir, and an optional serving
export (--lda-export) of the FrozenLDAModel.

    PYTHONPATH=src python -m repro.launch.train --lda --lda-topics 64
"""

from __future__ import annotations

import argparse
import sys


def train_lda(*, n_topics: int = 64, iters: int = 100, n_docs: int = 400,
              n_words: int = 800, mean_doc_len: int = 80,
              fmt: str = "dense", backend: str = "auto",
              balance: str = "none",
              checkpoint_dir: str | None = None,
              checkpoint_every: int | None = None, eval_every: int = 10,
              seed: int = 0, export_path: str | None = None,
              log_fn=print) -> dict:
    """The --lda mode: EZLDA training through the engine (DESIGN.md SS7).

    Builds a planted-topic synthetic corpus (the offline stand-in for the
    paper's corpora), trains it through ``LDAEngine.fit`` on the requested
    live-state format, and optionally exports the serving artifact.
    Returns the engine's history dict.
    """
    from repro.lda.api import LDAEngine
    from repro.lda.corpus import synthetic_lda_corpus
    from repro.lda.model import DistConfig, LDAConfig

    corpus = synthetic_lda_corpus(
        seed, n_docs=n_docs, n_words=n_words,
        n_topics=max(n_topics // 2, 2), mean_doc_len=mean_doc_len)
    cfg = LDAConfig(n_topics=n_topics, format=fmt, seed=seed,
                    eval_every=eval_every, dist=DistConfig(balance=balance))
    engine = LDAEngine(corpus, cfg, backend=backend,
                       checkpoint_dir=checkpoint_dir)
    log_fn(f"[lda] {corpus.n_docs} docs / {corpus.n_words} words / "
           f"{corpus.n_tokens} tokens, K={n_topics}, format={fmt}, "
           f"backend={engine.backend_name}")
    hist = engine.fit(iters, log_fn=lambda s: log_fn("[lda] " + s),
                      checkpoint_every=checkpoint_every)
    if hist["llpt"]:
        log_fn(f"[lda] done: llpt {hist['llpt'][0]:+.4f} -> "
               f"{hist['llpt'][-1]:+.4f} at iter {engine.iteration} "
               f"(live state {engine.state_nbytes():,} B)")
    else:
        log_fn(f"[lda] done: no iterations run (iter {engine.iteration})")
    if export_path:
        engine.export().save(export_path)
        log_fn(f"[lda] serving artifact written to {export_path}")
    return hist


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--lda", action="store_true",
                    help="run EZLDA topic-model training via LDAEngine "
                         "(the only mode; accepted for older command lines)")
    ap.add_argument("--checkpoint-dir", default=None)
    ap.add_argument("--checkpoint-every", type=int, default=None)
    ap.add_argument("--lda-topics", type=int, default=64)
    ap.add_argument("--lda-iters", type=int, default=100)
    ap.add_argument("--lda-docs", type=int, default=400)
    ap.add_argument("--lda-words", type=int, default=800)
    ap.add_argument("--lda-format", choices=("dense", "hybrid"),
                    default="dense")
    ap.add_argument("--lda-balance", choices=("none", "tiles"),
                    default="none",
                    help="hierarchical tile-scheduled workload balancing "
                         "(DESIGN.md SS9); pure perf knob, bit-equal")
    ap.add_argument("--lda-backend", choices=("auto", "single",
                                              "distributed"), default="auto")
    ap.add_argument("--lda-export", default=None, metavar="PATH",
                    help="write the FrozenLDAModel serving artifact here")
    args = ap.parse_args(argv)
    from repro.runtime.compile_cache import enable_compile_cache
    enable_compile_cache()
    hist = train_lda(n_topics=args.lda_topics, iters=args.lda_iters,
                     n_docs=args.lda_docs, n_words=args.lda_words,
                     fmt=args.lda_format, backend=args.lda_backend,
                     balance=args.lda_balance,
                     checkpoint_dir=args.checkpoint_dir,
                     checkpoint_every=args.checkpoint_every,
                     export_path=args.lda_export)
    return 0 if hist["llpt"] and hist["llpt"][-1] >= hist["llpt"][0] else 1


if __name__ == "__main__":
    sys.exit(main())
