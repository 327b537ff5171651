"""End-to-end training launcher (single host or forged-mesh dry runs).

Drives either kind of workload the framework supports:
  * --lda: the paper's EZLDA training (sample→update→LLPT) through the
    ``repro.lda.api.LDAEngine`` front door — backend auto-selected by
    device count, checkpoint/restart via --checkpoint-dir, and an
    optional serving export (--lda-export) of the FrozenLDAModel;
  * --arch <id>: LM pretraining on the synthetic pipeline (the ~100M
    example run is examples/lm_pretrain.py which calls into here).

On real hardware the same module runs under multi-host jax.distributed;
device/mesh selection stays in launch/mesh.py.
"""

from __future__ import annotations

import argparse
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.checkpoint import CheckpointManager
from repro.configs import REGISTRY
from repro.data.synthetic import make_batch
from repro.models.registry import get_model, reduced_config
from repro.train.optimizer import AdamWConfig
from repro.train.train_step import make_train_step


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
    paper's corpora), trains with the fused three-branch pipeline on the
    requested live-state format, and optionally exports the serving
    artifact. Returns the engine's history dict.
    """
    from repro.lda.api import LDAEngine
    from repro.lda.corpus import synthetic_lda_corpus
    from repro.lda.model import LDAConfig

    corpus = synthetic_lda_corpus(
        seed, n_docs=n_docs, n_words=n_words,
        n_topics=max(n_topics // 2, 2), mean_doc_len=mean_doc_len)
    cfg = LDAConfig(n_topics=n_topics, format=fmt, fused=True, seed=seed,
                    eval_every=eval_every, balance=balance)
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


def train_lm(arch: str, *, steps: int = 200, seq_len: int = 256,
             global_batch: int = 8, reduced: bool = True,
             checkpoint_dir: str | None = None, log_every: int = 10,
             lr: float = 3e-3, seed: int = 0, log_fn=print) -> dict:
    cfg = REGISTRY[arch]
    if reduced:
        cfg = reduced_config(cfg)
    api = get_model(cfg)
    from repro.runtime.compat import make_mesh as _make_mesh
    mesh = _make_mesh((1, jax.device_count()), ("data", "model")) \
        if jax.device_count() > 1 else _make_mesh((1, 1), ("data", "model"))
    opt = AdamWConfig(lr=lr, warmup_steps=max(steps // 20, 5),
                      total_steps=steps)
    step_fn, init_state = make_train_step(api, mesh, n_micro=1, opt_cfg=opt)
    jstep = jax.jit(step_fn, donate_argnums=(0,))
    manager = CheckpointManager(checkpoint_dir) if checkpoint_dir else None
    state = init_state(jax.random.PRNGKey(seed))
    start = 0
    if manager is not None:
        payload = manager.restore_latest()
        if payload is not None:
            start = int(payload["step"])
            log_fn(f"[train] resuming from step {start}")
    history = {"step": [], "loss": [], "tokens_per_sec": []}
    t0 = time.perf_counter()
    for i in range(start, steps):
        batch = {k: jnp.asarray(v) for k, v in make_batch(
            cfg, seq_len, global_batch, "train", step=i, seed=seed).items()}
        state, metrics = jstep(state, batch)
        if (i + 1) % log_every == 0 or i == start:
            jax.block_until_ready(metrics["loss"])
            dt = time.perf_counter() - t0
            tps = (i + 1 - start) * seq_len * global_batch / dt
            history["step"].append(i + 1)
            history["loss"].append(float(metrics["loss"]))
            history["tokens_per_sec"].append(tps)
            log_fn(f"[train] step={i+1:5d} loss={float(metrics['loss']):.4f}"
                   f" tok/s={tps:,.0f} lr={float(metrics['lr']):.2e}")
        if manager is not None and (i + 1) % 50 == 0:
            manager.save(i + 1, {"step": np.int64(i + 1)})
    return history


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--lda", action="store_true",
                    help="run EZLDA topic-model training via LDAEngine "
                         "instead of LM pretraining")
    ap.add_argument("--arch", choices=sorted(REGISTRY), default="qwen1.5-0.5b")
    ap.add_argument("--steps", type=int, default=200)
    ap.add_argument("--seq-len", type=int, default=256)
    ap.add_argument("--global-batch", type=int, default=8)
    ap.add_argument("--full-config", action="store_true",
                    help="use the published config (needs real accelerators)")
    ap.add_argument("--checkpoint-dir", default=None)
    ap.add_argument("--checkpoint-every", type=int, default=None)
    ap.add_argument("--lr", type=float, default=3e-3)
    # --lda knobs
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
    if args.lda:
        hist = train_lda(n_topics=args.lda_topics, iters=args.lda_iters,
                         n_docs=args.lda_docs, n_words=args.lda_words,
                         fmt=args.lda_format, backend=args.lda_backend,
                         balance=args.lda_balance,
                         checkpoint_dir=args.checkpoint_dir,
                         checkpoint_every=args.checkpoint_every,
                         export_path=args.lda_export)
        return 0 if hist["llpt"] and hist["llpt"][-1] >= hist["llpt"][0] \
            else 1
    hist = train_lm(args.arch, steps=args.steps, seq_len=args.seq_len,
                    global_batch=args.global_batch,
                    reduced=not args.full_config,
                    checkpoint_dir=args.checkpoint_dir, lr=args.lr)
    final = hist["loss"][-1] if hist["loss"] else float("nan")
    print(f"[train] done: final loss {final:.4f}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
