"""The LDA command line (``python -m repro.launch.train --lda``), end to
end in a subprocess at a tiny size: it trains through ``LDAEngine``'s
default path, prints its done line, and writes a loadable serving
artifact."""

import os
import pathlib
import subprocess
import sys

import numpy as np

from repro.lda.api import FrozenLDAModel

ROOT = pathlib.Path(__file__).resolve().parents[1]


def test_lda_command_line_trains_and_exports(tmp_path):
    out = tmp_path / "model.npz"
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path / "jax_cache"),
               PYTHONPATH=os.pathsep.join(
                   [str(ROOT / "src")]
                   + [p for p in [os.environ.get("PYTHONPATH")] if p]))
    proc = subprocess.run(
        [sys.executable, "-m", "repro.launch.train", "--lda",
         "--lda-topics", "8", "--lda-iters", "3", "--lda-docs", "40",
         "--lda-words", "60", "--lda-export", str(out)],
        capture_output=True, text=True, timeout=600, cwd=tmp_path, env=env)
    assert proc.returncode == 0, proc.stdout[-2000:] + proc.stderr[-4000:]
    assert "[lda] done: llpt" in proc.stdout, proc.stdout[-2000:]
    assert "backend=single" in proc.stdout, proc.stdout[-2000:]
    model = FrozenLDAModel.load(str(out))
    theta = np.asarray(model.transform([[1, 2, 3], [4, 4]]))
    assert theta.shape == (2, 8)
    assert np.allclose(theta.sum(axis=1), 1.0, atol=1e-5)
