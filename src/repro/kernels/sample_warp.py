"""Tile-scheduled WarpLDA MH sampling kernel (``sampler="warp"``).

Per grid step (one token tile) the kernel:

  1. **builds the tile's alias tables in VMEM** from the already-resident
     (win_words, K) word-run window of the scan-start W̃ — the locality
     WarpLDA's proposal tables need is exactly what the tile plan
     (core/balance.py, DESIGN.md SS9) already guarantees: every token in
     the tile draws from rows of one narrow window, so one O(win·K) build
     amortizes over every token in the tile. The pairing loop is the SAME
     Vose construction the XLA path runs (core/mh.run_vose) with one-hot
     writes instead of scatters (Mosaic has no scatter; a one-hot masked
     where stores bit-identical values), seeded by the precomputed
     small/large queue windows — sort-based queue metadata rides in with
     the window like the tile plan itself.
  2. **replays the word-proposal draws** against the tile tables. Tables
     are row-independent, so the in-kernel build equals the XLA global
     build sliced — the same (u₀, u₁) uniforms produce the same topics,
     which is what makes ``impl="pallas"`` bit-equal to ``impl="xla"``
     for the warp engine (pinned by tests/test_warp_sampler.py).
  3. **runs the accept/reject cycle** with one-hot column gathers from
     the resident D rows / Ŵ window / q̃ window — O(K) VPU lanes per
     token per proposal instead of the exact sampler's O(K) *sequential*
     cumsum + searchsorted sweep.

K is kept as one block (no k-blocking): the chain needs per-token column
gathers across the whole row, so every grid step holds four (win, K)
windows plus the Vose build's working set in VMEM. Compiled for a v5e
(Mosaic's default 16 MiB scoped VMEM, tile_t 256) the step needs about
10.7 MiB at win 256 × K 1,000 and 16.2 MiB at win 384, so at the
NYTimes width (K 1,000) windows up to 256 words fit. K ≥ 2,048 does not
fit at any window. ``_vmem_bytes_per_word`` is a bound fitted over those
compiles, and ``check_window`` turns it into an error at construction:
the cond's full-vocabulary branch compiles a (V, K) window, so a
vocabulary past the bound cannot run this kernel at all.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core import mh
from repro.kernels.runtime import resolve_interpret
from repro.kernels.sample_fused import window_rows

__all__ = ["sample_warp_tiled", "DEFAULT_TILE_T", "check_window"]

DEFAULT_TILE_T = 256
MAX_TOPICS = 1024                 # the (T, K) chain state outgrows VMEM past it
VMEM_LIMIT_BYTES = 16 << 20       # Mosaic's default scoped VMEM per step


def _vmem_bytes_per_word(n_topics: int) -> int:
    """Upper bound on one grid step's scoped VMEM per window word for
    K ≤ ``MAX_TOPICS``: about ten f32 words per window element at
    lane-padded K, plus the (win, 1) Vose columns, which Mosaic pads to
    128 lanes."""
    k_lanes = -(-n_topics // 128) * 128
    return 40 * (k_lanes + 256)


def check_window(win_words: int, n_topics: int) -> None:
    """Raise unless a ``win_words`` window at K topics fits in VMEM."""
    if n_topics > MAX_TOPICS:
        raise ValueError(
            f"sampler='warp' with impl='pallas' supports K <= {MAX_TOPICS} "
            f"(got K={n_topics}): the kernel keeps whole (tile, K) rows in "
            "VMEM. Use impl='xla' for the warp sampler at this K")
    per_word = _vmem_bytes_per_word(n_topics)
    need = win_words * per_word
    if need > VMEM_LIMIT_BYTES:
        raise ValueError(
            f"sampler='warp' with impl='pallas' needs a {win_words}-word "
            f"window at K={n_topics} in VMEM (~{need / 2**20:.1f} MiB, "
            f"limit {VMEM_LIMIT_BYTES / 2**20:.0f} MiB); at most "
            f"{VMEM_LIMIT_BYTES // per_word} words fit. The kernel's "
            "fallback branch holds the whole vocabulary, so this corpus "
            "cannot run it: use impl='xla' for the warp sampler")


def _col(mat, kvec):
    """One-hot column gather: mat[i, kvec[i]] per row for a (T, 1) column
    of topics — no scatter/gather, result a (T, 1) column."""
    kk = jax.lax.broadcasted_iota(jnp.int32, mat.shape, 1)
    sel = kk == kvec
    return jnp.sum(jnp.where(sel, mat, jnp.zeros_like(mat)), axis=1,
                   keepdims=True)


def _kernel(s_ref, local_ref, tdoc_ref, udraw_ref, uacc_ref, d_ref,
            wwin_ref, wtil_ref, squeue_ref, lqueue_ref, nsmall_ref,
            out_s_ref, out_acc_ref,
            *, k_total: int, n_cycles: int, alpha: float):
    # -- 1. per-tile alias tables from the resident W̃ window --------------
    wtil = wtil_ref[...]                                   # (win, K)
    q_win = wtil / jnp.sum(wtil, axis=1, keepdims=True)
    prob_win, alias_win = mh.run_vose(
        q_win * k_total, squeue_ref[...], lqueue_ref[...], nsmall_ref[...],
        onehot=True)

    # -- per-token row resolution from the window (two-level lookup) ------
    local = local_ref[...]                                 # (T, 1)
    w_rows = window_rows(local, wwin_ref[...])             # (T, K) live Ŵ
    q_rows = window_rows(local, q_win)                     # (T, K) stale q̃
    prob_rows = window_rows(local, prob_win)
    # alias ids < K ≤ 2^24 travel through the f32 matmul exactly
    alias_rows = window_rows(
        local, alias_win.astype(jnp.float32)).astype(jnp.int32)
    d_rows = d_ref[...]                                    # (T, K) int32

    s = s_ref[...]                                         # (T, 1)
    n_acc = jnp.zeros_like(s)
    udraw = udraw_ref[...]                                 # (T, 2C)
    uacc = uacc_ref[...]                                   # (T, 2C)
    tdoc = tdoc_ref[...]                                   # (T, C)
    for c in range(n_cycles):
        u0, u1 = udraw[:, 2 * c:2 * c + 1], udraw[:, 2 * c + 1:2 * c + 2]
        a0, a1 = uacc[:, 2 * c:2 * c + 1], uacc[:, 2 * c + 1:2 * c + 2]
        # doc proposal: the (D+α) factors cancel (core/mh.py docstring)
        t = tdoc[:, c:c + 1]
        acc = a0 * _col(w_rows, s) < _col(w_rows, t)
        n_acc += acc.astype(jnp.int32)
        s = jnp.where(acc, t, s)

        # -- 2. word proposal replayed against the tile tables ------------
        j = jnp.minimum((u0 * k_total).astype(jnp.int32), k_total - 1)
        keep = u1 < _col(prob_rows, j)
        t = jnp.where(keep, j, _col(alias_rows, j))

        # -- 3. accept against live counts, stale q̃ correction ------------
        num = (_col(d_rows, t).astype(jnp.float32) + alpha) \
            * _col(w_rows, t) * _col(q_rows, s)
        den = (_col(d_rows, s).astype(jnp.float32) + alpha) \
            * _col(w_rows, s) * _col(q_rows, t)
        acc = a1 * den < num
        n_acc += acc.astype(jnp.int32)
        s = jnp.where(acc, t, s)

    out_s_ref[...] = s
    out_acc_ref[...] = n_acc


@functools.partial(jax.jit,
                   static_argnames=("alpha", "n_cycles", "win_words",
                                    "tile_t", "interpret"))
def sample_warp_tiled(s0, d_rows, t_doc, u_draw, u_acc, w_hat, w_til,
                      squeue, lqueue, n_small, word_ids, first_word, *,
                      alpha: float, n_cycles: int, win_words: int,
                      tile_t: int = DEFAULT_TILE_T,
                      interpret: bool | None = None):
    """MH warp chain for a token chunk against one word-run window.

    Args:
      s0: (N,) int32 iteration-start topics of the chunk tokens.
      d_rows: (N, K) int32 pre-gathered D rows (iteration-start counts).
      t_doc: (C, N) int32 positional doc proposals (mh.doc_proposals).
      u_draw: (C, 2, N) f32 word-draw uniforms (mh.word_proposals).
      u_acc: (C, 2, N) f32 acceptance uniforms.
      w_hat: (V, K) f32 live Ŵ; w_til: (V, K) f32 scan-start W̃ the
        tables are built from (equal on the scan's first iteration).
      squeue/lqueue/n_small: Vose queue metadata for W̃ (mh.alias_queues
        on the scaled rows — sort-based, so computed once per scan
        outside the kernel and windowed here like the tile plan).
      word_ids: (N,) int32; first_word: () int32 tile word-run start.
    Returns:
      (topics (N,) int32, accepted-proposal counts (N,) int32) — bit-equal
      to the XLA chunk path on the same uniforms.
    """
    interpret = resolve_interpret(interpret)
    n, k_total = d_rows.shape
    v_total = w_hat.shape[0]
    win = int(min(win_words, v_total))
    first = jnp.clip(jnp.asarray(first_word, jnp.int32), 0, v_total - win)
    slc = lambda m: jax.lax.dynamic_slice(m, (first, 0), (win, k_total))
    w_win, t_win = slc(w_hat), slc(w_til)
    sq_win, lq_win = slc(squeue), slc(lqueue)
    ns_win = jax.lax.dynamic_slice(n_small, (first,), (win,))[:, None]
    local = jnp.clip(word_ids.astype(jnp.int32) - first, 0, win - 1)

    c = t_doc.shape[0]
    tdoc_t = jnp.transpose(t_doc)                          # (N, C)
    udraw_t = jnp.transpose(u_draw, (2, 0, 1)).reshape(n, 2 * c)
    uacc_t = jnp.transpose(u_acc, (2, 0, 1)).reshape(n, 2 * c)

    n_pad = (-n) % tile_t
    if n_pad:
        pad1 = lambda a: jnp.pad(a, (0, n_pad))
        s0, local = pad1(s0), pad1(local)
        d_rows = jnp.pad(d_rows, ((0, n_pad), (0, 0)))
        tdoc_t = jnp.pad(tdoc_t, ((0, n_pad), (0, 0)))
        udraw_t = jnp.pad(udraw_t, ((0, n_pad), (0, 0)))
        uacc_t = jnp.pad(uacc_t, ((0, n_pad), (0, 0)))
    n_tiles = s0.shape[0] // tile_t

    kernel = functools.partial(_kernel, k_total=k_total,
                               n_cycles=int(c), alpha=float(alpha))
    tok_spec = pl.BlockSpec((tile_t, 1), lambda t: (t, 0))
    tokc_spec = pl.BlockSpec((tile_t, 2 * c), lambda t: (t, 0))
    tokd_spec = pl.BlockSpec((tile_t, c), lambda t: (t, 0))
    mat_spec = pl.BlockSpec((tile_t, k_total), lambda t: (t, 0))
    win_spec = pl.BlockSpec((win, k_total), lambda t: (0, 0))
    win1_spec = pl.BlockSpec((win, 1), lambda t: (0, 0))
    s, n_acc = pl.pallas_call(
        kernel,
        grid=(n_tiles,),
        in_specs=[tok_spec, tok_spec, tokd_spec, tokc_spec, tokc_spec,
                  mat_spec, win_spec, win_spec, win_spec, win_spec,
                  win1_spec],
        out_specs=(tok_spec, tok_spec),
        out_shape=(jax.ShapeDtypeStruct((n_tiles * tile_t, 1), jnp.int32),
                   jax.ShapeDtypeStruct((n_tiles * tile_t, 1), jnp.int32)),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
    )(s0[:, None], local[:, None], tdoc_t, udraw_t, uacc_t, d_rows, w_win,
      t_win, sq_win, lq_win, ns_win)
    return s[:n, 0], n_acc[:n, 0]
