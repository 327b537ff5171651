"""Every Pallas kernel entry point compiles for a TPU v5e at real widths.

Nothing runs: each kernel is lowered and compiled by the TPU compiler
for a described (not attached) ``v5e:2x2`` chip, at the shapes the
NYTimes deployment (V = 101,636, K = 1,000) hands it — 8,192-token tiles
(the trainer's ``tile_size``), a 256-word tile window, and the hybrid
state's packed D width (416 slots, padded to 512 lanes by the wrapper).
``sample_fused`` is also compiled at K = 10,000. Each compiled program
must hold the Mosaic kernel (``tpu_custom_call``): a kernel the chip's
compiler refuses fails here, at no chip time. The data-parallel
iteration's two programs are compiled for the whole 2x2 mesh at one
chip's share of the PubMed deployment, where the collectives must sit.

The topology is described inside a module fixture, never at import, so
only the worker that runs this file loads the TPU compiler.
"""

from __future__ import annotations

import re

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P, SingleDeviceSharding

from repro.kernels import histogram, sample_fused, sample_sparse, sample_warp

V = 101_636
K = 1_000
N = 8_192          # LDAConfig.tile_size: the token tile every kernel sees
L = 416            # hybrid packed-D slots at NYTimes doc lengths
WIN = 256          # tile word window (sample_warp's VMEM bound at K=1,000)
ALPHA = 0.05
f32, i32 = jnp.float32, jnp.int32


@pytest.fixture(scope="module")
def topo():
    import os
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means "no TPU compiler"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip cannot be read back without one:
    # keep the persistent cache out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _compile(one_chip, fn, *shapes):
    args = [jax.ShapeDtypeStruct(s, dt, sharding=one_chip)
            for s, dt in shapes]
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("k", [K, 10_000])
def test_sample_fused(one_chip, k):
    _compile(one_chip,
             lambda u, d, w: sample_fused.sample_fused(
                 u, d, w, alpha=ALPHA, interpret=False),
             ((N,), f32), ((N, k), i32), ((N, k), f32))


def test_sample_fused_tiled(one_chip):
    _compile(one_chip,
             lambda u, d, w, v, first: sample_fused.sample_fused_tiled(
                 u, d, w, v, first, alpha=ALPHA, win_words=WIN,
                 interpret=False),
             ((N,), f32), ((N, K), i32), ((V, K), f32), ((N,), i32),
             ((), i32))


def test_sample_sparse(one_chip):
    _compile(one_chip,
             lambda u, p, w, k1, a1, b1, q: sample_sparse.sample_sparse(
                 u, p, w, k1, a1, b1, q, alpha=ALPHA, interpret=False),
             ((N,), f32), ((N, L), i32), ((N, L), f32), ((N,), i32),
             ((N,), f32), ((N,), f32), ((N,), f32))


def test_sample_sparse_tiled(one_chip):
    _compile(one_chip,
             lambda u, p, w, v, first, k1, a1, q, b1:
                 sample_sparse.sample_sparse_tiled(
                     u, p, w, v, first, k1, a1, q, b1, alpha=ALPHA,
                     win_words=WIN, interpret=False),
             ((N,), f32), ((N, L), i32), ((N, L), f32), ((N,), i32),
             ((), i32), ((V,), i32), ((V,), f32), ((V,), f32), ((N,), f32))


def test_sample_warp_tiled(one_chip):
    sample_warp.check_window(WIN, K)           # the bound admits this window
    c = 2                                      # LDAConfig.mh_cycles
    _compile(one_chip,
             lambda *a: sample_warp.sample_warp_tiled(
                 *a, alpha=ALPHA, n_cycles=c, win_words=WIN,
                 interpret=False),
             ((N,), i32), ((N, K), i32), ((c, N), i32), ((c, 2, N), f32),
             ((c, 2, N), f32), ((V, K), f32), ((V, K), f32), ((V, K), i32),
             ((V, K), i32), ((V,), i32), ((N,), i32), ((), i32))


def test_histogram_partials(one_chip):
    tile_t = histogram.DEFAULT_TILE_T
    _compile(one_chip,
             lambda r, t, w, b: histogram.histogram_partials(
                 r, t, w, b, n_topics=K, interpret=False),
             ((N,), i32), ((N,), i32), ((N,), i32), ((N // tile_t,), i32))


# one chip's share of the pubmed-k1k-dp4 deployment's (4, 1) data mesh:
# a quarter of its 46,172,698 tokens, padded to 1,024 slots, and of its
# 512,500 documents, at PubMed's vocabulary
N_SHARD = 11_544_576
M_SHARD = 128_125
V_PUBMED = 141_043
COLLECTIVE = re.compile(r"\b(all-reduce|all-gather|reduce-scatter|"
                        r"collective-permute|all-to-all)")


def test_data_parallel_programs(topo):
    """The data-parallel iteration's draw holds no collective, and its
    update holds the (V, K) W-delta all-reduce, on a v5e 2x2 mesh."""
    from repro.core import three_branch
    from repro.lda import distributed
    from repro.lda.model import LDAConfig
    mesh = Mesh(np.asarray(topo.devices).reshape(4, 1), ("data", "model"))
    cfg = LDAConfig(n_topics=K, alpha=ALPHA, beta=0.01)
    sample, update = distributed.dp_programs(
        mesh, three_branch.plan_for(N_SHARD, cfg), cfg.alpha_)

    def arg(shape, dtype, *spec):
        return jax.ShapeDtypeStruct(shape, dtype,
                                    sharding=NamedSharding(mesh, P(*spec)))

    tok = arg((4, N_SHARD), i32, "data")
    D = arg((4, M_SHARD, K), i32, "data", None, "model")
    key_t = jax.eval_shape(jax.random.key, 0)
    key = arg(key_t.shape, key_t.dtype)
    stats = three_branch.ThreeBranchStats(*[arg((4,), f32, "data")] * len(
        three_branch.ThreeBranchStats._fields))
    drawn = jax.jit(sample).lower(
        tok, tok, tok, arg((4, N_SHARD), jnp.uint32, "data"), tok, D,
        arg((V_PUBMED, K), f32, None, "model"), key).compile().as_text()
    assert COLLECTIVE.search(drawn) is None
    counted = jax.jit(update).lower(
        tok, tok, tok, tok, tok, D, arg((V_PUBMED, K), i32, None, "model"),
        stats, key, arg((), i32)).compile().as_text()
    assert any(COLLECTIVE.search(line) and f"s32[{V_PUBMED},{K}]" in line
               for line in counted.splitlines()), "no (V, K) all-reduce"
