"""Fused three-branch sampling kernel (dense-word hot path).

The paper's sampling kernel builds S/Q max-trees per token and descends them
(warp-parallel, §II-B Fig 2). The TPU adaptation (DESIGN.md §2) streams the
K axis through VMEM in blocks and replaces tree descent with a two-phase
sweep over a fused pallas grid ``(token_tiles, phase, k_blocks)``:

  phase 0 — branch masses: running (a1, K1, b1) max-carry + ΣD∘Ŵ + ΣŴ
            accumulated in VMEM scratch. At the end of the sweep we have,
            per token: M = a1·(b1+α), S' = ΣD∘Ŵ − a1·b1,
            Q' = α·(ΣŴ − a1)  (Eq 6/8, exact — no estimate needed here).
  phase 1 — inverse-CDF: x = u·(M+S'+Q'); if x < M the token lands in the M
            branch (topic K1, "skipped final sampling"). Otherwise one
            *combined* sweep over k≠K1 with per-topic mass (D[k]+α)·Ŵ[k]
            accumulates a running cumsum until it crosses x−M.

The combined sweep is a TPU-native simplification: the paper keeps S' and Q'
as two separate trees because S' is sparse on GPU; per-topic the combined
mass is (D+α)∘Ŵ' = p_s' + p_q' exactly, so one pass draws from the identical
distribution (tests pin this against ref.three_branch_masses/ref oracles).

Two entry points share the phase body:

``sample_fused``       — the (D rows, Ŵ rows) inputs arrive pre-gathered per
  token: the gather is the inverted-index-driven part that XLA does well;
  the O(T·K) arithmetic + reduction is the part that wants MXU/VPU block
  residency.

``sample_fused_tiled`` — the tile-scheduled variant (paper §V-A made live,
  DESIGN.md SS9): the caller supplies the FULL Ŵ matrix plus the tile's
  word-run metadata (``first_word`` and the static window ``win_words`` =
  the plan's ``max_words_per_tile`` bound), and the kernel resolves each
  token's Ŵ row from a per-tile word WINDOW held in VMEM — one
  (win_words, K) slice per tile instead of one (T, K) gather per token.
  This is the two-level (word, region) index analogue: within a tile every
  token of the same word reads the same resident row. Scratch/window size
  is bounded by the tile plan's ``max_words_per_tile``, exactly the
  paper's per-block shared-memory budget. Bit-exact vs ``sample_fused``
  (same f32 row values ⇒ identical arithmetic), pinned by
  tests/test_balance.py.

VMEM budget per grid step: 2 · TILE_T · BLOCK_K · 4 B (D and Ŵ blocks)
+ O(TILE_T) scratch (+ win_words · BLOCK_K · 4 B for the tiled window).
Defaults (128 × 512) use 512 KB — well under 16 MB, leaving room for
double buffering.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.runtime import resolve_interpret

__all__ = ["sample_fused", "sample_fused_tiled", "lane_prefix_sum",
           "first_lane", "window_rows", "DEFAULT_TILE_T", "DEFAULT_BLOCK_K"]

DEFAULT_TILE_T = 128
DEFAULT_BLOCK_K = 512
_NEG_INF = -1e30  # python float: jnp module-level consts can't be captured


def lane_prefix_sum(x):
    """Inclusive prefix sum along the lane (last) axis of a 2-D block.

    Mosaic has no ``cumsum``; this is the log-step (Hillis–Steele) scan:
    ceil(log2(width)) lane rotations on the XLU plus masked adds on the
    VPU. Shared by the fused and sparse kernels so both accumulate their
    CDFs in the same order.
    """
    width = x.shape[1]
    lane = jax.lax.broadcasted_iota(jnp.int32, x.shape, 1)
    shift = 1
    while shift < width:
        x = x + jnp.where(lane >= shift, pltpu.roll(x, shift, 1),
                          jnp.zeros_like(x))
        shift *= 2
    return x


def first_lane(hit):
    """Index of the first true lane per row (``width`` when none is)."""
    lane = jax.lax.broadcasted_iota(jnp.int32, hit.shape, 1)
    return jnp.min(jnp.where(hit, lane, hit.shape[1]), axis=1, keepdims=True)


def _phase_body(phase, kb, d, w, valid,                 # per-block values
                u_ref, topic_ref, m_ref, s_ref, q_ref,  # token-tile refs
                amax, bmax, kmax, sum_s, sum_q, cum, target, found, cand,
                *, block_k: int, n_kblocks: int, k_total: int, alpha: float):
    """The shared two-phase sweep over one (token tile, k block) step.

    ``d``/``w`` are the resolved (T, BK) blocks — pre-gathered rows for the
    plain kernel, window-resolved rows for the tiled kernel. Everything
    downstream is identical, which is what makes the two entry points
    bit-equal. Per-token state lives in (T, 1) columns: Mosaic broadcasts
    a column across lanes, but cannot reshape a 1-D vector into one.
    """
    lane = jax.lax.broadcasted_iota(jnp.int32, d.shape, 1)
    k_global = kb * block_k + lane                     # (T, BK)

    @pl.when((phase == 0) & (kb == 0))
    def _init():
        amax[...] = jnp.full_like(amax[...], _NEG_INF)
        bmax[...] = jnp.zeros_like(bmax[...])
        kmax[...] = jnp.zeros_like(kmax[...])
        sum_s[...] = jnp.zeros_like(sum_s[...])
        sum_q[...] = jnp.zeros_like(sum_q[...])

    @pl.when(phase == 0)
    def _masses():
        wv = jnp.where(valid, w, _NEG_INF)
        blk_max = jnp.max(wv, axis=1, keepdims=True)   # (T, 1)
        blk_arg = first_lane(wv == blk_max)            # argmax, first tie
        blk_d = jnp.sum(jnp.where(lane == blk_arg, d, 0.0), axis=1,
                        keepdims=True)
        better = blk_max > amax[...]
        amax[...] = jnp.where(better, blk_max, amax[...])
        kmax[...] = jnp.where(better, kb * block_k + blk_arg, kmax[...])
        bmax[...] = jnp.where(better, blk_d, bmax[...])
        wz = jnp.where(valid, w, 0.0)
        sum_s[...] += jnp.sum(d * wz, axis=1, keepdims=True)
        sum_q[...] += jnp.sum(wz, axis=1, keepdims=True)

    @pl.when((phase == 1) & (kb == 0))
    def _finalize_masses():
        a1 = amax[...]
        b1 = bmax[...]
        m = a1 * (b1 + alpha)                          # Eq 8
        s_p = sum_s[...] - a1 * b1                     # exact S'
        q_p = alpha * (sum_q[...] - a1)                # exact Q'
        m_ref[...] = m
        s_ref[...] = s_p
        q_ref[...] = q_p
        x = u_ref[...] * (m + s_p + q_p)
        target[...] = x - m                            # combined-CDF target
        found[...] = (x < m).astype(jnp.int32)         # M branch ⇒ K1
        cand[...] = kmax[...]
        cum[...] = jnp.zeros_like(cum[...])

    @pl.when(phase == 1)
    def _cdf():
        mass = (d + alpha) * w
        mass = jnp.where(valid & (k_global != kmax[...]), mass, 0.0)
        c = cum[...] + lane_prefix_sum(mass)           # (T, BK)
        # only a lane with mass may be drawn: the log-step scan is not
        # monotone to the last ulp, and a padding lane is not a topic
        hit = (c > target[...]) & (mass > 0)
        first = first_lane(hit)
        any_hit = first < block_k
        take = (found[...] == 0) & any_hit
        cand[...] = jnp.where(take, kb * block_k + first, cand[...])
        found[...] = jnp.where(any_hit, 1, found[...])
        cum[...] = jnp.sum(jnp.where(lane == block_k - 1, c, 0.0), axis=1,
                           keepdims=True)

        @pl.when(kb == n_kblocks - 1)
        def _emit():
            # numerical tail guard: u ≈ 1 with float cumsum undershoot —
            # clamp to the last valid topic (measure-zero event)
            topic_ref[...] = jnp.where(found[...] != 0, cand[...],
                                       k_total - 1)


def _kernel(u_ref, d_ref, w_ref,                       # inputs
            topic_ref, m_ref, s_ref, q_ref,            # outputs
            amax, bmax, kmax, sum_s, sum_q, cum, target, found, cand,
            *, block_k: int, n_kblocks: int, k_total: int, alpha: float):
    phase = pl.program_id(1)
    kb = pl.program_id(2)
    d = d_ref[...].astype(jnp.float32)                 # (T, BK)
    w = w_ref[...]                                     # (T, BK)
    k_global = kb * block_k + jax.lax.broadcasted_iota(
        jnp.int32, d.shape, dimension=1)
    valid = k_global < k_total                         # tail-block mask
    _phase_body(phase, kb, d, w, valid,
                u_ref, topic_ref, m_ref, s_ref, q_ref,
                amax, bmax, kmax, sum_s, sum_q, cum, target, found, cand,
                block_k=block_k, n_kblocks=n_kblocks, k_total=k_total,
                alpha=alpha)


def window_rows(local, window):
    """Rows ``window[local[t]]`` for a (T, 1) column of window offsets.

    Mosaic has no row gather, so this is a one-hot (T, win) @ (win, BK)
    matmul on the MXU. Every output is one exact product plus exact
    zeros, and HIGHEST precision keeps the f32 values whole, so the rows
    are bit-equal to a gather.
    """
    onehot = (local == jax.lax.broadcasted_iota(
        jnp.int32, (local.shape[0], window.shape[0]), 1)).astype(window.dtype)
    return jax.lax.dot(onehot, window, precision=jax.lax.Precision.HIGHEST,
                       preferred_element_type=window.dtype)


def _tiled_kernel(u_ref, local_ref, d_ref, wwin_ref,   # inputs
                  topic_ref, m_ref, s_ref, q_ref,      # outputs
                  amax, bmax, kmax, sum_s, sum_q, cum, target, found, cand,
                  *, block_k: int, n_kblocks: int, k_total: int,
                  alpha: float):
    phase = pl.program_id(1)
    kb = pl.program_id(2)
    d = d_ref[...].astype(jnp.float32)                 # (T, BK)
    # resolve each token's Ŵ row from the tile's resident word window —
    # the two-level (word, region) lookup
    w = window_rows(local_ref[...], wwin_ref[...])     # (T, BK)
    k_global = kb * block_k + jax.lax.broadcasted_iota(
        jnp.int32, d.shape, dimension=1)
    valid = k_global < k_total
    _phase_body(phase, kb, d, w, valid,
                u_ref, topic_ref, m_ref, s_ref, q_ref,
                amax, bmax, kmax, sum_s, sum_q, cum, target, found, cand,
                block_k=block_k, n_kblocks=n_kblocks, k_total=k_total,
                alpha=alpha)


def _scratch(tile_t: int):
    col = lambda dt: pltpu.VMEM((tile_t, 1), dt)  # noqa: E731
    return [col(jnp.float32)] * 2 + [col(jnp.int32)] \
        + [col(jnp.float32)] * 4 + [col(jnp.int32)] * 2


def _out_shapes(n: int):
    return (
        jax.ShapeDtypeStruct((n, 1), jnp.int32),    # topic
        jax.ShapeDtypeStruct((n, 1), jnp.float32),  # M
        jax.ShapeDtypeStruct((n, 1), jnp.float32),  # S'
        jax.ShapeDtypeStruct((n, 1), jnp.float32),  # Q'
    )


def _columns(outs, n: int):
    return tuple(o[:n, 0] for o in outs)


@functools.partial(jax.jit,
                   static_argnames=("alpha", "tile_t", "block_k", "interpret"))
def sample_fused(u: jax.Array, d_rows: jax.Array, w_rows: jax.Array, *,
                 alpha: float, tile_t: int = DEFAULT_TILE_T,
                 block_k: int = DEFAULT_BLOCK_K,
                 interpret: bool | None = None):
    """Sample topics for a token batch from pre-gathered (D, Ŵ) rows.

    Args:
      u: (N,) uniforms in [0,1).
      d_rows: (N, K) int32 — D[doc_ids] gathered rows.
      w_rows: (N, K) f32 — Ŵ[word_ids] gathered rows.
      interpret: None resolves via runtime.interpret_default(), so direct
        callers compile to Mosaic on TPU instead of silently interpreting.
    Returns:
      topics (N,) int32 and the exact branch masses (M, S', Q') per token.
    """
    interpret = resolve_interpret(interpret)
    n, k_total = d_rows.shape
    n_pad = (-n) % tile_t
    k_pad = (-k_total) % block_k
    if n_pad or k_pad:
        u = jnp.pad(u, (0, n_pad))
        d_rows = jnp.pad(d_rows, ((0, n_pad), (0, k_pad)))
        w_rows = jnp.pad(w_rows, ((0, n_pad), (0, k_pad)))
    n_tiles = u.shape[0] // tile_t
    n_kblocks = w_rows.shape[1] // block_k

    grid = (n_tiles, 2, n_kblocks)
    kernel = functools.partial(
        _kernel, block_k=block_k, n_kblocks=n_kblocks, k_total=k_total,
        alpha=float(alpha))
    tok_spec = pl.BlockSpec((tile_t, 1), lambda t, p, kb: (t, 0))
    mat_spec = pl.BlockSpec((tile_t, block_k), lambda t, p, kb: (t, kb))
    outs = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[tok_spec, mat_spec, mat_spec],
        out_specs=(tok_spec, tok_spec, tok_spec, tok_spec),
        out_shape=_out_shapes(n_tiles * tile_t),
        scratch_shapes=_scratch(tile_t),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary", "arbitrary")),
        name="sample_fused",
        interpret=interpret,
    )(u[:, None], d_rows, w_rows)
    return _columns(outs, n)


@functools.partial(jax.jit,
                   static_argnames=("alpha", "win_words", "tile_t", "block_k",
                                    "interpret"))
def sample_fused_tiled(u: jax.Array, d_rows: jax.Array, w_hat: jax.Array,
                       word_ids: jax.Array, first_word: jax.Array, *,
                       alpha: float, win_words: int,
                       tile_t: int = DEFAULT_TILE_T,
                       block_k: int = DEFAULT_BLOCK_K,
                       interpret: bool | None = None):
    """Tile-scheduled sample_fused: Ŵ rows resolved from a word window.

    The tile's word-run metadata (``first_word`` .. ``first_word +
    win_words``) selects ONE (win_words, K) window of Ŵ for the whole
    token batch; each token reads its row by local offset inside the
    kernel. ``win_words`` is static — the tile plan's
    ``max_words_per_tile`` bound (pow2-bucketed by the pipeline) — so the
    window is the kernel's shared-memory analogue. Callers guarantee
    every token's word lies inside the window (the pipeline cond-guards
    on the measured span and falls back to ``sample_fused`` otherwise);
    out-of-window ids are clipped, which only matters for tokens a caller
    already masked out.

    Args:
      u: (N,) uniforms; d_rows: (N, K) int32 pre-gathered D rows.
      w_hat: (V, K) f32 — the FULL Ŵ matrix (not per-token rows).
      word_ids: (N,) int32 token word ids (word-sorted within the tile).
      first_word: () int32 — first word id of the tile's run.
    Returns:
      (topics, M, S', Q') — bit-equal to ``sample_fused`` on the gathered
      rows.
    """
    interpret = resolve_interpret(interpret)
    n, k_total = d_rows.shape
    v_total = w_hat.shape[0]
    win = int(min(win_words, v_total))
    first = jnp.clip(jnp.asarray(first_word, jnp.int32), 0, v_total - win)
    window = jax.lax.dynamic_slice(w_hat, (first, 0), (win, k_total))
    local = jnp.clip(word_ids.astype(jnp.int32) - first, 0, win - 1)

    n_pad = (-n) % tile_t
    k_pad = (-k_total) % block_k
    if n_pad or k_pad:
        u = jnp.pad(u, (0, n_pad))
        local = jnp.pad(local, (0, n_pad))
        d_rows = jnp.pad(d_rows, ((0, n_pad), (0, k_pad)))
    # lane-align the window's rows: they are the one-hot matmul's
    # contraction axis (padding rows are never selected)
    window = jnp.pad(window, ((0, (-win) % 128), (0, k_pad)))
    n_tiles = u.shape[0] // tile_t
    n_kblocks = window.shape[1] // block_k

    grid = (n_tiles, 2, n_kblocks)
    kernel = functools.partial(
        _tiled_kernel, block_k=block_k, n_kblocks=n_kblocks,
        k_total=k_total, alpha=float(alpha))
    tok_spec = pl.BlockSpec((tile_t, 1), lambda t, p, kb: (t, 0))
    mat_spec = pl.BlockSpec((tile_t, block_k), lambda t, p, kb: (t, kb))
    win_spec = pl.BlockSpec((window.shape[0], block_k),
                            lambda t, p, kb: (0, kb))
    outs = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[tok_spec, tok_spec, mat_spec, win_spec],
        out_specs=(tok_spec, tok_spec, tok_spec, tok_spec),
        out_shape=_out_shapes(n_tiles * tile_t),
        scratch_shapes=_scratch(tile_t),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary", "arbitrary")),
        name="sample_fused_tiled",
        interpret=interpret,
    )(u[:, None], local[:, None], d_rows, window)
    return _columns(outs, n)
