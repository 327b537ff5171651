"""Where the code runs: interpret-mode resolution, the compile cache's
placement, the warp kernel's VMEM bound at construction, and an
Explicit-axes mesh passed to the engine."""

from __future__ import annotations

import os
import pathlib
import subprocess
import sys
import textwrap

import pytest

import jax

from repro.kernels import runtime
from repro.lda.api import LDAEngine
from repro.lda.corpus import relabel_by_frequency, zipf_corpus
from repro.lda.model import LDAConfig
from repro.runtime import compile_cache

ROOT = pathlib.Path(__file__).resolve().parents[1]


@pytest.mark.parametrize("backend,expect", [("cpu", True), ("tpu", False),
                                            ("gpu", None)])
def test_interpret_resolves_by_backend(monkeypatch, backend, expect):
    monkeypatch.setattr(runtime.jax, "default_backend", lambda: backend)
    if expect is None:
        with pytest.raises(RuntimeError, match="compile only for a TPU"):
            runtime.resolve_interpret(None)
    else:
        assert runtime.resolve_interpret(None) is expect
    assert runtime.resolve_interpret(True) is True      # explicit wins
    assert runtime.resolve_interpret(False) is False


def test_default_cache_dir_is_fixed_inside_the_checkout():
    assert compile_cache.DEFAULT_CACHE_DIR == ROOT / ".jax_cache"
    ignored = (ROOT / ".gitignore").read_text().splitlines()
    assert ".jax_cache/" in ignored, ".jax_cache must be gitignored"


_CACHE_PROBE = textwrap.dedent("""
    import sys; sys.path.insert(0, "src")
    import jax, jax.numpy as jnp
    from repro.runtime.compile_cache import enable_compile_cache
    print(enable_compile_cache())
    print(jax.config.jax_compilation_cache_dir)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.jit(lambda x: jnp.sin(x) * 3)(jnp.arange(5.0)).block_until_ready()
""")


def _probe(**env):
    base = {k: v for k, v in os.environ.items()
            if k != compile_cache.CACHE_ENV}
    proc = subprocess.run([sys.executable, "-c", _CACHE_PROBE], cwd=ROOT,
                          capture_output=True, text=True, timeout=300,
                          env={**base, "JAX_PLATFORMS": "cpu", **env})
    assert proc.returncode == 0, proc.stderr[-3000:]
    return proc.stdout.split()


def test_cache_goes_where_the_environment_says(tmp_path):
    target = tmp_path / "cache"
    default = compile_cache.DEFAULT_CACHE_DIR
    before = set(default.iterdir()) if default.exists() else set()
    returned, configured = _probe(JAX_COMPILATION_CACHE_DIR=str(target))
    assert returned == configured == str(target)
    assert any(target.iterdir()), "nothing was cached in the env's dir"
    after = set(default.iterdir()) if default.exists() else set()
    assert after == before, "the in-checkout default was written too"


def test_cache_defaults_to_the_checkout():
    returned, configured = _probe()
    assert returned == configured == str(compile_cache.DEFAULT_CACHE_DIR)


def test_warp_pallas_rejects_a_vocabulary_past_its_window_bound():
    corpus, _ = relabel_by_frequency(
        zipf_corpus(0, n_docs=30, n_words=400, mean_doc_len=20))
    cfg = LDAConfig(n_topics=1000, sampler="warp", impl="pallas",
                    fused=True)
    with pytest.raises(ValueError, match="window at K=1000"):
        LDAEngine(corpus, cfg, backend="single")


@pytest.mark.slow
def test_engine_accepts_an_explicit_axes_mesh():
    """A mesh with Explicit axes (jax.make_mesh's default) trains, scores
    and exports through the distributed backend, dense and hybrid."""
    code = textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
        import sys; sys.path.insert(0, "src")
        import numpy as np, jax
        from repro.lda.api import LDAEngine
        from repro.lda.corpus import relabel_by_frequency, zipf_corpus
        from repro.lda.model import LDAConfig
        from repro.runtime.compat import make_mesh
        corpus, _ = relabel_by_frequency(
            zipf_corpus(0, n_docs=40, n_words=120, mean_doc_len=30))
        for fmt in ("dense", "hybrid"):
            mesh = make_mesh((4, 1), ("data", "model"), explicit=True)
            eng = LDAEngine(corpus, LDAConfig(n_topics=8, tile_size=256,
                                              format=fmt),
                            mesh=mesh, pad_multiple=256)
            eng.fit(2)
            D, W = eng.trainer.gather_global(eng.state)
            assert D.sum() == W.sum() == corpus.n_tokens, fmt
            assert np.isfinite(eng.score()), fmt
            assert eng.export().W.sum() == corpus.n_tokens, fmt
        print("OK")
    """)
    proc = subprocess.run([sys.executable, "-c", code], cwd=ROOT,
                          capture_output=True, text=True, timeout=600,
                          env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert proc.returncode == 0 and "OK" in proc.stdout, proc.stderr[-4000:]
