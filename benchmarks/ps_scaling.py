"""Parameter-server W sharding: per-host W bytes and scaling vs replicated.

No single paper figure — EZLDA's §V-B distributed scheme replicates the
full (V, K) word-topic matrix W on every data shard and all-reduces the
per-iteration delta, so per-host W memory is flat in the worker count.
``DistConfig(w_sync="ps")`` (DESIGN.md SS15) is the other strategy: W is
split into contiguous word-range *owner* shards, each worker pulls only
the row pages its token sub-shards touch and pushes int32 delta blocks
back under a stale-synchronous round clock. This driver measures, per
forged worker count (subprocesses — the forged device count must be set
before jax initializes):

  * the largest owner shard's bytes vs one replicated W copy
    (acceptance bar at the top worker count: <= 0.35x — the point of
    sharding W is that per-host model memory FALLS as hosts are added);
  * per-host live count-state bytes (worker D block + largest owner)
    vs the replicated trainer's per-host state;
  * round throughput for both strategies (PS pays host-side page
    traffic; the number is reported, not gated — on one real CPU the
    forged workers time-slice a single core);
  * a bitwise trained-state parity check at ``staleness=0`` against the
    replicated psum path on the same corpus and seed (the invariant
    tests/test_ps.py pins; gated here so the committed numbers can
    never drift from a config where it stopped holding).

``--dry-run`` shrinks everything to a seconds-long smoke (the CI hook)
but still writes the same JSON schema.

The children are structure checks on forged CPU devices by design: they
run with ``JAX_PLATFORMS=cpu``, so on an accelerator host they never
contend with the parent for the chip.

Emits results/BENCH_ps_scaling.json (schema in docs/BENCHMARKS.md,
gated by tools/check_bench.py).
Run:  PYTHONPATH=src python benchmarks/ps_scaling.py [--dry-run]
"""

from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys

_SCRIPT = r"""
import json, os, sys, time
p = json.loads(sys.argv[1])
os.environ["XLA_FLAGS"] = \
    "--xla_force_host_platform_device_count=%d" % p["n_workers"]
sys.path.insert(0, "src")
import jax
import numpy as np
from repro.lda.api import LDAEngine
from repro.lda.corpus import relabel_by_frequency, zipf_corpus
from repro.lda.model import DistConfig, LDAConfig
from repro.runtime.compat import make_mesh

corpus = zipf_corpus(3, n_docs=p["n_docs"], n_words=p["n_words"],
                     exponent=1.25, mean_doc_len=p["doc_len"])
corpus, _ = relabel_by_frequency(corpus)
mesh = make_mesh((p["n_workers"], 1), ("data", "model"))
kw = dict(n_topics=p["k"], tile_size=p["tile"], seed=7)
tr_r = LDAEngine(corpus, LDAConfig(**kw), backend="distributed",
                 mesh=mesh, pad_multiple=p["pad"]).trainer
tr_p = LDAEngine(corpus, LDAConfig(**kw, dist=DistConfig(w_sync="ps")),
                 backend="distributed", mesh=mesh,
                 pad_multiple=p["pad"]).trainer

# -- warm to the converged regime + the staleness=0 parity pin -------------
s_r, _ = tr_r.run_fused(tr_r.init_state(), p["warmup"])
s_p, _ = tr_p.run_fused(tr_p.init_state(), p["warmup"])
D_r, W_r = tr_r.gather_global(s_r)
D_p, W_p = tr_p.gather_global(s_p)
bitwise = bool(np.array_equal(np.asarray(W_r), W_p)
               and np.array_equal(np.asarray(D_r), D_p))
tr_p.selfcheck(s_p)

# -- throughput: interleaved repeats, medians ------------------------------
ts_r, ts_p = [], []
for _ in range(p["repeats"]):
    t0 = time.perf_counter()
    s_r, _ = tr_r.run_fused(s_r, p["timed"])
    jax.block_until_ready(s_r.W)
    ts_r.append(corpus.n_tokens * p["timed"] / (time.perf_counter() - t0))
    t0 = time.perf_counter()
    s_p, _ = tr_p.run_fused(s_p, p["timed"])   # host-synchronous rounds
    ts_p.append(corpus.n_tokens * p["timed"] / (time.perf_counter() - t0))

srv = s_p.server
print(json.dumps({
    "n_workers": p["n_workers"],
    "n_tokens": int(corpus.n_tokens),
    "n_owners": srv.layout.n_owners,
    "replicated_w_bytes": int(np.asarray(W_r).nbytes),
    "max_owner_bytes": int(srv.max_owner_nbytes()),
    "per_host_state_bytes": int(tr_p.state_nbytes(s_p)),
    "replicated_state_bytes": int(tr_r.state_nbytes(s_r)),
    "replicated_tokens_per_sec": float(np.median(ts_r)),
    "ps_tokens_per_sec": float(np.median(ts_p)),
    "bitwise_equal_to_replicated": bitwise,
}))
"""


def bench(out_path: str = "results/BENCH_ps_scaling.json",
          dry_run: bool = False) -> dict:
    if dry_run:
        worker_counts = (2,)
        params = dict(n_docs=40, n_words=150, doc_len=30, k=8,
                      tile=256, pad=64, warmup=1, timed=1, repeats=1)
    else:
        # model-dominated enough that W sharding is the visible win: W is
        # (2000, 32) vs ~5 KB of per-worker D rows at 8 workers
        worker_counts = (2, 4, 8)
        params = dict(n_docs=240, n_words=2000, doc_len=100, k=32,
                      tile=4096, pad=256, warmup=3, timed=3, repeats=3)

    cells = []
    for n in worker_counts:
        arg = json.dumps({**params, "n_workers": n})
        proc = subprocess.run([sys.executable, "-c", _SCRIPT, arg],
                              capture_output=True, text=True, timeout=1800,
                              env={**os.environ, "JAX_PLATFORMS": "cpu"})
        if proc.returncode != 0:
            raise RuntimeError(
                f"ps_scaling cell n_workers={n} failed:\n"
                + proc.stderr[-4000:])
        r = json.loads(proc.stdout.strip().splitlines()[-1])
        r["owner_frac"] = r["max_owner_bytes"] / r["replicated_w_bytes"]
        r["state_frac"] = (r["per_host_state_bytes"]
                           / r["replicated_state_bytes"])
        r["ps_over_replicated"] = (r["ps_tokens_per_sec"]
                                   / r["replicated_tokens_per_sec"])
        cells.append(r)

    top = cells[-1]
    result = {
        "dry_run": dry_run,
        "corpus": {"docs": params["n_docs"], "words": params["n_words"],
                   "tokens": int(top["n_tokens"])},
        "n_topics": params["k"],
        "warmup_iters": params["warmup"], "timed_iters": params["timed"],
        "repeats": params["repeats"],
        "cells": cells,
        "max_workers": top["n_workers"],
        # the headline: per-host W bytes at the top worker count
        "owner_frac_at_max": top["owner_frac"],
        "staleness0_bitwise": all(c["bitwise_equal_to_replicated"]
                                  for c in cells),
    }
    if os.path.dirname(out_path):
        os.makedirs(os.path.dirname(out_path), exist_ok=True)
    with open(out_path, "w") as f:
        json.dump(result, f, indent=2)
    return result


def run():
    """benchmarks/run.py entry: CSV rows (name, us_per_call, derived)."""
    r = bench()
    for c in r["cells"]:
        n = c["n_workers"]
        yield (f"ps_scaling/workers{n}_owner_frac", 0.0,
               round(c["owner_frac"], 4))
        yield (f"ps_scaling/workers{n}_ps_tokens_per_sec", 0.0,
               round(c["ps_tokens_per_sec"], 0))
        yield (f"ps_scaling/workers{n}_bitwise", 0.0,
               int(c["bitwise_equal_to_replicated"]))
    yield ("ps_scaling/owner_frac_at_max", 0.0,
           round(r["owner_frac_at_max"], 4))


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--dry-run", action="store_true",
                    help="seconds-long smoke with tiny sizes (CI)")
    ap.add_argument("--out", default="results/BENCH_ps_scaling.json")
    args = ap.parse_args()
    print(json.dumps(bench(out_path=args.out, dry_run=args.dry_run),
                     indent=2))
