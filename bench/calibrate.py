#!/usr/bin/env python3
"""Readings that the check's limits are set from, at a cell's own size.

    python bench/calibrate.py --workload <cell> --seeds 11 12 13 --control 3

For each seed, in one process: the cell's set-up, checked iteration and
window (``--seconds``, the benchmark's ``run_seconds`` by default), then
the numbers the check compares, for

* the program;
* the control (the first ``--control`` seeds): the reference computed in
  bfloat16, a precision below the configuration's float32, put in the
  program's place: its draws at the checked iteration, and its LLPT of the
  reference's topics where the window reported one;
* the faults a training cell can have, planted in the program's output at
  the checked iteration and at the window's last: a step that returns its
  state unchanged, half of the tokens left unsampled, and every 64th
  token's topic altered where it is drawn.

One JSON line per seed on standard output. The benchmark's own runs never
run this.
"""

from __future__ import annotations

import argparse
import gc
import json
import pathlib
import sys
import time

import numpy as np

ROOT = pathlib.Path(__file__).resolve().parents[1]


def planted(snap, win, sample, n_topics) -> dict:
    """The program's compared topics with each fault planted: (the checked
    iteration's, the window's last iteration's)."""
    z0, z1 = snap.topics[0][sample], snap.topics[1][sample]
    zf = win.topics[sample]
    half = sample < snap.topics[0].shape[0] // 2
    alt = sample % 64 == 0
    return {
        "unchanged": (z0, z1),
        "half_unsampled": (np.where(half, z0, z1), np.where(half, z1, zf)),
        "altered": (np.where(alt, (z1 + 1) % n_topics, z1),
                    np.where(alt, (zf + 1) % n_topics, zf)),
    }


def readings(g, cfg, snap, win, sample, control: bool) -> dict:
    import jax.numpy as jnp
    from bench import harness, reference
    rep = harness.replay(g, cfg, snap, win, sample)
    res = {f"{k}.program": v
           for k, v in harness.compare(rep, snap, win, sample).items()}
    for name, (step, last) in planted(snap, win, sample, g.n_topics).items():
        res[f"topic_mismatch.{name}"] = reference.mismatch(step, rep.step)
        res[f"window_mismatch.{name}"] = reference.mismatch(last, rep.window)
    if control:
        low, _ = rep.ref.next_topics(snap.topics[0], snap.key0,
                                     np.asarray(sample, np.int32),
                                     dtype=jnp.bfloat16)
        res["topic_mismatch.control"] = reference.mismatch(
            np.asarray(low)[:, 0], rep.step)
        res["llpt_gap.control"] = max(
            abs(rep.ref.llpt(z, jnp.bfloat16) - rep.llpt[it])
            / abs(rep.llpt[it]) for it, z in rep.chain.items())
    return res


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--control", type=int, default=3)
    ap.add_argument("--seconds", type=float, default=None)
    args = ap.parse_args(argv)

    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    from bench import harness
    harness.enable_compile_cache()

    cell = harness.load_cell(args.workload)
    harness.devices(cell.chips, require_tpu=True)
    seconds = args.seconds or json.loads(
        (ROOT / "BENCHMARK.json").read_text())["run_seconds"]
    clock = harness.CompileClock()
    for i, seed in enumerate(args.seeds):
        t0 = time.perf_counter()
        g, corpus = harness.make_corpus(cell, seed)
        payload = harness.warm_payload(g, cell, seed) \
            if cell.traffic["init"] == "planted" else None
        engine = harness.make_engine(corpus, cell)
        del corpus
        snap = harness.checked_step(engine, cell.traffic, payload)
        win = harness.measure(engine, clock,
                              harness.window_iters(cell, seconds))
        harness.keep_final(engine, win)
        del engine
        gc.collect()
        t1 = time.perf_counter()
        res = readings(g, cell.config, snap, win,
                       harness.check_sample(g.n_tokens, seed),
                       control=i < args.control)
        res.update(workload=args.workload, seed=seed, window_s=win.wall,
                   compiles_in_window=win.compiles, setup_s=t1 - t0,
                   reference_s=time.perf_counter() - t1)
        print(json.dumps(res), flush=True)
        del g, snap, win
        gc.collect()
    return 0


if __name__ == "__main__":
    sys.exit(main())
