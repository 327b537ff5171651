"""Sparse-S' sampling kernel (tail-word path, paper §IV-C).

For tail words the D rows are bucketed-ELL sparse (L slots ≪ K). The paper
densifies Ŵ[v] into shared memory and scans the sparse D row; here the roles
are TPU-arranged: the (idx, val) slots and the Ŵ values *gathered at those
slots* live in VMEM for a token tile, so S' construction + the S'-branch
inverse-CDF cost O(L) per token instead of O(K) — that is the entire point
of the paper's sparse format.

Pair-unpacking happens inside the kernel: the packed int32 ELL row
(idx<<16 | val, §IV-B) is the wire/HBM format; the kernel splits it with the
same shift/mask arithmetic the paper's CUDA kernel uses.

Tokens whose draw lands in the Q' branch (mass α·ΣŴ', no dependence on D)
are flagged via ``needs_q`` and finished by the caller against the per-word
Q table — they are rare once training converges (S' ≫ Q' for converged
tokens) and batchable per word.

``sample_sparse_tiled`` is the tile-scheduled variant (paper §V-A made
live, DESIGN.md SS9): the per-WORD quantities (K1, a1, Q') arrive as one
(win_words,) window per tile — the tile plan's ``max_words_per_tile``
bound — and each token resolves them by local word offset inside the
kernel, instead of the caller gathering them per token. b1 = D[d][K1]
stays per-token (it depends on the document). Bit-equal to
``sample_sparse`` on the gathered values.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.runtime import resolve_interpret
from repro.kernels.sample_fused import first_lane, lane_prefix_sum

__all__ = ["sample_sparse", "sample_sparse_tiled"]

DEFAULT_TILE_T = 256


def _draw(u, packed, w_at, k1, a1, b1, qp,
          topic_ref, needs_q_ref, s_ref, *, alpha: float):
    """Shared O(L) three-branch draw body (plain and tiled kernels).

    Per-token values are (T, 1) columns; slot arrays are (T, L).
    """
    # 16/16 pair unpack (paper §IV-B) in int32: shift, then mask the sign
    # extension away (Mosaic has no unsigned-to-float cast)
    idx = (packed >> 16) & 0xFFFF
    val = (packed & 0xFFFF).astype(jnp.float32)
    m = a1 * (b1 + alpha)                                 # Eq 8
    w_eff = jnp.where(idx == k1, 0.0, w_at)               # zero the K1 slot
    p_s = val * w_eff
    cdf = lane_prefix_sum(p_s)
    lane = jax.lax.broadcasted_iota(jnp.int32, idx.shape, 1)
    s_p = jnp.sum(jnp.where(lane == idx.shape[1] - 1, cdf, 0.0), axis=1,
                  keepdims=True)
    x = u * (m + s_p + qp)
    in_m = x < m
    # only a slot with mass may be drawn: the log-step scan is not monotone
    # to the last ulp, and an empty slot's idx is not a topic
    slot = first_lane((cdf > (x - m)) & (p_s > 0))
    found = slot < idx.shape[1]
    rows_sel = jnp.sum(jnp.where(lane == slot, idx, 0), axis=1,
                       keepdims=True)
    in_s = jnp.logical_and(jnp.logical_not(in_m),
                           jnp.logical_and(found, x < m + s_p))
    needs_q = jnp.logical_and(jnp.logical_not(in_m), jnp.logical_not(in_s))
    topic_ref[...] = jnp.where(in_m, k1, jnp.where(in_s, rows_sel, -1))
    needs_q_ref[...] = needs_q.astype(jnp.int32)
    s_ref[...] = s_p


def _kernel(u_ref, packed_ref, w_ref, k1_ref, a1_ref, b1_ref, qp_ref,
            topic_ref, needs_q_ref, s_ref, *, alpha: float):
    _draw(u_ref[...], packed_ref[...], w_ref[...], k1_ref[...], a1_ref[...],
          b1_ref[...], qp_ref[...], topic_ref, needs_q_ref, s_ref,
          alpha=alpha)


def _window_lookup(local, row):
    """``row[0, local[t]]`` for a (T, 1) column of window offsets: a
    one-hot select-and-sum over the (1, win) row (exact: one value plus
    zeros), since Mosaic gathers only along 2-D rows."""
    lane = jax.lax.broadcasted_iota(jnp.int32, (local.shape[0],
                                                row.shape[1]), 1)
    return jnp.sum(jnp.where(lane == local, row, jnp.zeros_like(row)),
                   axis=1, keepdims=True)


def _tiled_kernel(u_ref, packed_ref, w_ref, local_ref, b1_ref,
                  k1w_ref, a1w_ref, qpw_ref,
                  topic_ref, needs_q_ref, s_ref, *, alpha: float):
    # per-word stats resolved from the tile's word window (two-level index)
    local = local_ref[...]
    k1 = _window_lookup(local, k1w_ref[...])
    a1 = _window_lookup(local, a1w_ref[...])
    qp = _window_lookup(local, qpw_ref[...])
    _draw(u_ref[...], packed_ref[...], w_ref[...], k1, a1, b1_ref[...], qp,
          topic_ref, needs_q_ref, s_ref, alpha=alpha)


def _out_shapes(n: int):
    return (
        jax.ShapeDtypeStruct((n, 1), jnp.int32),
        jax.ShapeDtypeStruct((n, 1), jnp.int32),
        jax.ShapeDtypeStruct((n, 1), jnp.float32),
    )


def _lane_pad(rows, n_pad: int):
    """Pad (N, L) slot rows to a tile multiple of tokens and a lane-aligned
    slot count; the extra slots are (idx 0, val 0) and carry no mass."""
    return jnp.pad(rows, ((0, n_pad), (0, (-rows.shape[1]) % 128)))


def _unpack_outs(outs, n: int):
    topics, needs_q, s_p = (o[:n, 0] for o in outs)
    return topics, needs_q != 0, s_p


@functools.partial(jax.jit, static_argnames=("alpha", "tile_t", "interpret"))
def sample_sparse(u: jax.Array, packed_rows: jax.Array, w_at_idx: jax.Array,
                  k1: jax.Array, a1: jax.Array, b1: jax.Array,
                  q_prime: jax.Array, *, alpha: float,
                  tile_t: int = DEFAULT_TILE_T,
                  interpret: bool | None = None):
    """O(L)-per-token three-branch sampling over packed ELL D rows.

    Args:
      u: (N,) uniforms; packed_rows: (N, L) int32 ELL (idx<<16|val);
      w_at_idx: (N, L) Ŵ[v] gathered at the row's idx slots;
      k1/a1/b1/q_prime: per-token word/doc stats (gathered by the caller).
    Returns:
      (topics, needs_q, s_prime); topics = -1 where needs_q.
    """
    interpret = resolve_interpret(interpret)
    n = packed_rows.shape[0]
    n_pad = (-n) % tile_t
    col = lambda a, fill=0: jnp.pad(a, (0, n_pad),  # noqa: E731
                                    constant_values=fill)[:, None]
    packed_rows = _lane_pad(packed_rows, n_pad)
    w_at_idx = _lane_pad(w_at_idx, n_pad)
    n_tiles = (n + n_pad) // tile_t
    tok = pl.BlockSpec((tile_t, 1), lambda t: (t, 0))
    mat = pl.BlockSpec((tile_t, packed_rows.shape[1]), lambda t: (t, 0))
    outs = pl.pallas_call(
        functools.partial(_kernel, alpha=float(alpha)),
        grid=(n_tiles,),
        in_specs=[tok, mat, mat, tok, tok, tok, tok],
        out_specs=(tok, tok, tok),
        out_shape=_out_shapes(n_tiles * tile_t),
        name="sample_sparse",
        interpret=interpret,
    )(col(u), packed_rows, w_at_idx, col(k1), col(a1, 1.0), col(b1),
      col(q_prime))
    return _unpack_outs(outs, n)


@functools.partial(jax.jit,
                   static_argnames=("alpha", "win_words", "tile_t",
                                    "interpret"))
def sample_sparse_tiled(u: jax.Array, packed_rows: jax.Array,
                        w_at_idx: jax.Array, word_ids: jax.Array,
                        first_word: jax.Array, k1_w: jax.Array,
                        a1_w: jax.Array, q_prime_w: jax.Array,
                        b1: jax.Array, *, alpha: float, win_words: int,
                        tile_t: int = DEFAULT_TILE_T,
                        interpret: bool | None = None):
    """Tile-scheduled sample_sparse: per-word stats from a word window.

    Args:
      u/packed_rows/w_at_idx/b1: per-token, as in ``sample_sparse``.
      word_ids: (N,) int32 token word ids; first_word: () int32 tile run
        start; win_words: static window size (plan's max_words_per_tile).
      k1_w/a1_w/q_prime_w: (V,) per-WORD stat vectors — the kernel reads
        the tile's (win_words,) window of each.
    Returns:
      (topics, needs_q, s_prime) — bit-equal to ``sample_sparse`` on the
      per-token gathered stats.
    """
    interpret = resolve_interpret(interpret)
    n = packed_rows.shape[0]
    v_total = k1_w.shape[0]
    win = int(min(win_words, v_total))
    first = jnp.clip(jnp.asarray(first_word, jnp.int32), 0, v_total - win)
    # each window as one lane-aligned (1, win) row; padding lanes are
    # never selected
    row = lambda a: jnp.pad(  # noqa: E731
        jax.lax.dynamic_slice(a, (first,), (win,)), (0, (-win) % 128))[None]
    k1_win, a1_win, qp_win = row(k1_w), row(a1_w), row(q_prime_w)
    local = jnp.clip(word_ids.astype(jnp.int32) - first, 0, win - 1)
    n_pad = (-n) % tile_t
    col = lambda a: jnp.pad(a, (0, n_pad))[:, None]  # noqa: E731
    packed_rows = _lane_pad(packed_rows, n_pad)
    w_at_idx = _lane_pad(w_at_idx, n_pad)
    n_tiles = (n + n_pad) // tile_t
    tok = pl.BlockSpec((tile_t, 1), lambda t: (t, 0))
    mat = pl.BlockSpec((tile_t, packed_rows.shape[1]), lambda t: (t, 0))
    win_spec = pl.BlockSpec((1, k1_win.shape[1]), lambda t: (0, 0))
    outs = pl.pallas_call(
        functools.partial(_tiled_kernel, alpha=float(alpha)),
        grid=(n_tiles,),
        in_specs=[tok, mat, mat, tok, tok, win_spec, win_spec, win_spec],
        out_specs=(tok, tok, tok),
        out_shape=_out_shapes(n_tiles * tile_t),
        name="sample_sparse_tiled",
        interpret=interpret,
    )(col(u), packed_rows, w_at_idx, col(local), col(b1), k1_win, a1_win,
      qp_win)
    return _unpack_outs(outs, n)
