"""Fig 16: multi-device scaling of EZLDA (paper: 3.3-3.4× on 4 GPUs).

Runs the shard_map trainer on 1/2/4/8 forged host devices in subprocesses
(the forged device count must be set before jax init). On one real CPU
core the wall-clock does not speed up — the reported metric is the
*structural* one the dry-run validates at 256/512 chips: per-device token
throughput normalized by shard count, plus token conservation.

The children are structure checks on forged CPU devices by design: they
run with ``JAX_PLATFORMS=cpu``, so on an accelerator host they never
contend with the parent for the chip. A failed child fails the run.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys

_SCRIPT = r"""
import os, sys, json, time
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=%d"
sys.path.insert(0, "src")
import jax, numpy as np, jax.numpy as jnp
from repro.lda.corpus import synthetic_lda_corpus, relabel_by_frequency
from repro.lda.model import LDAConfig
from repro.lda.api import LDAEngine
n_dev = %d
corpus = synthetic_lda_corpus(0, n_docs=240, n_words=300, n_topics=8,
                              mean_doc_len=60)
corpus, _ = relabel_by_frequency(corpus)
from repro.runtime.compat import make_mesh
mesh = make_mesh((n_dev, 1), ("data", "model"))
tr = LDAEngine(corpus, LDAConfig(n_topics=16), backend="distributed",
               mesh=mesh, pad_multiple=256).trainer
state = tr.init_state()
state, _ = tr.step(state)                       # compile
t0 = time.perf_counter()
for _ in range(5):
    state, stats = tr.step(state)
jax.block_until_ready(state.W)
dt = time.perf_counter() - t0
D, W = tr.gather_global(state)
imb = tr.sc.tokens_per_shard.max() / max(tr.sc.tokens_per_shard.mean(), 1)
print(json.dumps({
    "tokens_per_sec": corpus.n_tokens * 5 / dt,
    "conserved": bool(D.sum() == corpus.n_tokens == W.sum()),
    "chunk_imbalance": float(imb),
}))
"""


def run():
    rows = []
    for n_dev in (1, 2, 4, 8):
        proc = subprocess.run(
            [sys.executable, "-c", _SCRIPT % (n_dev, n_dev)],
            capture_output=True, text=True, timeout=600,
            env={**os.environ, "JAX_PLATFORMS": "cpu"})
        if proc.returncode != 0:
            raise RuntimeError(f"fig16 child on {n_dev} forged devices "
                               f"failed:\n{proc.stderr[-4000:]}")
        r = json.loads(proc.stdout.strip().splitlines()[-1])
        assert r["conserved"]
        rows.append((f"fig16/devices{n_dev}_tokens_per_sec", 0.0,
                     round(r["tokens_per_sec"], 0)))
        rows.append((f"fig16/devices{n_dev}_chunk_imbalance", 0.0,
                     round(r["chunk_imbalance"], 4)))
    return rows
