#!/usr/bin/env python3
"""How far training from cold takes a cell's skip share, beside the share
its planted state (the ``warm`` traffic) starts from.

    python bench/skip_curve.py --workload pubmed-k1k.warm --seed 7 --minutes 14

One process, with the cell's own corpus and engine settings (K, α, β only):
``LDAEngine.fit`` from the engine's random init, in calls of ten iterations,
until the skip share rose less than ``--flat`` points over the last
``--span`` iterations or ``--minutes`` have passed; then the planted
assignment restored on a fresh engine, and two iterations. One JSON line per
evaluated iteration, then one for the planted state. The benchmark's own
runs never run this.
"""

from __future__ import annotations

import argparse
import gc
import json
import pathlib
import sys
import time

ROOT = pathlib.Path(__file__).resolve().parents[1]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--minutes", type=float, default=14.0)
    ap.add_argument("--flat", type=float, default=0.5)
    ap.add_argument("--span", type=int, default=30)
    args = ap.parse_args(argv)

    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    from bench import harness
    harness.enable_compile_cache()

    cell = harness.load_cell(args.workload)
    harness.devices(cell.chips, require_tpu=True)
    g, corpus = harness.make_corpus(cell, args.seed)

    def emit(source, hist, t0):
        for it, ll, st in zip(hist["iteration"], hist["llpt"], hist["stats"]):
            curve.append((it, 100.0 * st["frac_skipped"]))
            print(json.dumps({"state": source, "iteration": it,
                              "skip_frac": curve[-1][1], "llpt": ll,
                              "elapsed_s": time.perf_counter() - t0}),
                  flush=True)

    curve: list = []
    engine = harness.make_engine(corpus, cell)
    t0 = time.perf_counter()
    while time.perf_counter() - t0 < 60.0 * args.minutes:
        emit("cold", engine.fit(10), t0)
        it, share = curve[-1]
        before = [s for i, s in curve if i <= it - args.span]
        if before and share - before[-1] < args.flat:
            break
    del engine
    gc.collect()

    curve = []
    engine = harness.make_engine(corpus, cell)
    engine.restore(harness.warm_payload(g, cell, args.seed))
    emit("planted", engine.fit(2), time.perf_counter())
    return 0


if __name__ == "__main__":
    sys.exit(main())
