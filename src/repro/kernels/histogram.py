"""MXU histogram kernel: count-matrix rebuild from the token list (§IV-C).

The update task rebuilds W (V×K) and D (M×K) from T after sampling. A
scatter-add is gather/serial on TPU; the MXU-native form is a double-one-hot
matmul per token tile:

    partial[r, k] = Σ_tokens 1[row_id − row_base == r] · 1[topic == k]
                  = onehot_rows(T×R)ᵀ @ onehot_topics(T×K_blk)

T is sorted by word (and doc-major via the inverted index for D), so each
tile touches a *contiguous, usually tiny* row range [row_base, row_base+R).
The kernel emits per-tile (R × K) partials; a cheap XLA segment-add folds
them into the full matrix. Tokens whose row falls outside the tile's R-row
window (rare: only ultra-ragged tail tiles) are masked out here and handled
by the caller's scatter fallback — mirroring the paper's W_dense-fast /
W_sparse-rebuild split.

MXU shape note: the matmul contracts over the token axis (TILE_T multiple of
128); R and K_blk are lane-aligned multiples of 128.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.runtime import resolve_interpret

__all__ = ["histogram_partials", "histogram"]

DEFAULT_TILE_T = 512
DEFAULT_ROWS = 128


def _kernel(base_ref, row_ref, topic_ref, weight_ref, out_ref, covered_ref,
            *, rows_per_tile: int, block_k: int):
    rows = row_ref[...]                                    # (1, T) int32
    topics = topic_ref[...]                                # (T, 1) int32
    w = weight_ref[...]                                    # (1, T) int32
    base = base_ref[pl.program_id(0)]                      # SMEM scalar
    rel = rows - base
    in_win = jnp.logical_and(rel >= 0, rel < rows_per_tile)
    use = jnp.logical_and(in_win, w > 0)                   # (1, T)
    kb = pl.program_id(1)
    t_rel = topics - kb * block_k                          # (T, 1)
    n_tok = topics.shape[0]
    # double one-hot (f32 for the MXU; counts are exact in f32 ≪ 2^24).
    # The row one-hot is built transposed, (R, T), so the matmul contracts
    # its minor axis; a topic outside this k block matches no lane.
    oh_r = jnp.logical_and(rel == jax.lax.broadcasted_iota(
        jnp.int32, (rows_per_tile, n_tok), 0), use).astype(jnp.float32)
    oh_k = (t_rel == jax.lax.broadcasted_iota(
        jnp.int32, (n_tok, block_k), 1)).astype(jnp.float32)
    out_ref[0] = jax.lax.dot(
        oh_r, oh_k, preferred_element_type=jnp.float32).astype(jnp.int32)
    # tokens this tile could NOT cover (row outside window) are the
    # caller's fallback scatter; the set is the same for every k block.
    covered_ref[...] = use.astype(jnp.int32)


@functools.partial(jax.jit, static_argnames=(
    "n_topics", "tile_t", "rows_per_tile", "block_k", "interpret"))
def histogram_partials(row_ids: jax.Array, topics: jax.Array,
                       weights: jax.Array, tile_bases: jax.Array, *,
                       n_topics: int, tile_t: int = DEFAULT_TILE_T,
                       rows_per_tile: int = DEFAULT_ROWS,
                       block_k: int = 512, interpret: bool | None = None):
    """Per-tile (R×K) one-hot MXU partial histograms + coverage mask."""
    interpret = resolve_interpret(interpret)
    n = row_ids.shape[0]
    if n % tile_t:
        raise ValueError(f"{n} tokens are not a multiple of tile_t={tile_t}:"
                         " pad the token list to whole tiles first")
    n_tiles = n // tile_t
    block_k = min(block_k, n_topics)
    k_pad = (-n_topics) % block_k
    n_kblocks = (n_topics + k_pad) // block_k
    # the tile bases ride in as scalar prefetch (SMEM), one per tile
    row_spec = pl.BlockSpec((1, tile_t), lambda t, kb, bases: (0, t))
    col_spec = pl.BlockSpec((tile_t, 1), lambda t, kb, bases: (t, 0))
    out_spec = pl.BlockSpec((1, rows_per_tile, block_k),
                            lambda t, kb, bases: (t, 0, kb))
    partials, covered = pl.pallas_call(
        functools.partial(_kernel, rows_per_tile=rows_per_tile,
                          block_k=block_k),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(n_tiles, n_kblocks),
            in_specs=[row_spec, col_spec, row_spec],
            out_specs=(out_spec, row_spec)),
        out_shape=(
            jax.ShapeDtypeStruct((n_tiles, rows_per_tile,
                                  n_kblocks * block_k), jnp.int32),
            jax.ShapeDtypeStruct((1, n), jnp.int32),
        ),
        interpret=interpret,
    )(tile_bases, row_ids[None], topics[:, None], weights[None])
    return partials[:, :, :n_topics], covered[0] != 0


def histogram(row_ids: jax.Array, topics: jax.Array, weights: jax.Array, *,
              n_rows: int, n_topics: int, tile_t: int = DEFAULT_TILE_T,
              rows_per_tile: int = DEFAULT_ROWS,
              interpret: bool | None = None):
    """Full count rebuild: MXU partials + segment-add + scatter fallback.

    ``row_ids`` should be sorted (word-sorted T for W; doc-major order via
    the inverted index for D) so tiles have narrow row windows.
    """
    n = row_ids.shape[0]
    n_pad = (-n) % tile_t
    if n_pad:
        row_ids = jnp.pad(row_ids, (0, n_pad))
        topics = jnp.pad(topics, (0, n_pad))
        weights = jnp.pad(weights, (0, n_pad))
    n_tiles = row_ids.shape[0] // tile_t
    tile_bases = row_ids[::tile_t]                        # first row per tile
    partials, covered = histogram_partials(
        row_ids, topics, weights, tile_bases, n_topics=n_topics,
        tile_t=tile_t, rows_per_tile=rows_per_tile, interpret=interpret)
    # Fold partials: out[base_t + r] += partial[t, r]  (n_tiles·R rows)
    out = jnp.zeros((n_rows + rows_per_tile, n_topics), jnp.int32)
    scatter_rows = (tile_bases[:, None]
                    + jnp.arange(rows_per_tile)[None, :]).reshape(-1)
    out = out.at[scatter_rows].add(
        partials.reshape(-1, n_topics), mode="drop")
    # Fallback scatter for the (rare) tokens outside their tile's window.
    left = jnp.logical_and(jnp.logical_not(covered), weights > 0)
    out = out.at[row_ids, topics].add(
        jnp.where(left, weights, 0).astype(jnp.int32), mode="drop")
    return out[:n_rows]
