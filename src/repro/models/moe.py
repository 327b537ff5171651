"""Mixture-of-Experts FFN with sort-based capacity dispatch.

This is where the paper's systems insight transfers (DESIGN.md §7): expert
load under a learned router is power-law-skewed exactly like tokens-per-word
(paper Fig 8). The fixes rhyme:

  * **capacity factor** = large-word dissection: no expert (word) may claim
    more than C slots per step; overflow is dropped (the LM analogue of
    re-chunking), keeping every schedulable unit equal-sized;
  * **sort-by-expert** = the word-sorted token list: one argsort turns
    ragged expert groups into contiguous runs, so dispatch is two static
    scatters instead of per-token pointer chasing;
  * the (E, C, D) expert buffers shard over the model axis (expert
    parallelism) like W's topic blocks.

Shapes are fully static: T tokens × top-k assignments → (E, C+1, D) buffers
(slot C is the overflow dump row). DeepSeek-style shared experts run as a
dense SwiGLU alongside the routed path.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import shard_map as _shard_map

from repro.models import layers
from repro.models.config import ModelConfig
from repro.runtime.sharding import constrain

__all__ = ["init_moe", "moe_ffn", "router_load_stats"]


def init_moe(key, cfg: ModelConfig) -> dict:
    d, f, e = cfg.d_model, cfg.moe_d_ff, cfg.padded_experts
    ks = jax.random.split(key, 5)
    dt = cfg.dtype
    def expert_w(k, din, dout):
        return (jax.random.truncated_normal(k, -2, 2, (e, din, dout),
                                            jnp.float32)
                * (din ** -0.5)).astype(dt)
    p = {
        "router": (jax.random.normal(ks[0], (d, e), jnp.float32)
                   * 0.02).astype(jnp.float32),           # router in f32
        "w_gate": expert_w(ks[1], d, f),
        "w_up": expert_w(ks[2], d, f),
        "w_down": expert_w(ks[3], f, d),
    }
    if cfg.n_shared_experts:
        p["shared"] = layers.init_mlp(
            ks[4], d, cfg.n_shared_experts * f, dt, act="silu")
    return p


def _capacity(cfg: ModelConfig, t: int, k: int, e: int) -> int:
    """Expert capacity: cf·T·k/E for production sizes; lossless (T·k) for
    small batches — decode must never drop a request's token."""
    if t * k <= 4096:
        return t * k
    return max(int(cfg.capacity_factor * t * k / e), 8)


def _dispatch_compute_combine(xf, router, w_gate, w_up, w_down, *,
                              cap: int, k: int, e_base, e_total: int):
    """Local sort-based dispatch for the experts [e_base, e_base+e_loc).

    Runs on one shard's tokens against one shard's expert slice; assignments
    to other shards' experts fall into the dump row. Pure function of local
    data — the shard_map wrapper below psums the partial outputs.
    """
    t, d = xf.shape
    e_loc = w_gate.shape[0]
    logits = xf.astype(jnp.float32) @ router               # (T, E_pad)
    # pad experts (expert_pad_multiple sharding) are dead: never routed
    logits = jnp.where(jnp.arange(logits.shape[-1]) < e_total, logits,
                       -1e30)
    probs = jax.nn.softmax(logits, axis=-1)
    w_topk, sel = jax.lax.top_k(probs, k)                  # (T, k)
    w_topk = w_topk / jnp.sum(w_topk, axis=-1, keepdims=True)

    flat_e = sel.reshape(-1)
    rel = flat_e - e_base                                  # my expert index
    mine = (rel >= 0) & (rel < e_loc)
    rel = jnp.where(mine, rel, e_loc)                      # e_loc = foreign
    order = jnp.argsort(rel, stable=True)
    sorted_rel = rel[order]
    starts = jnp.searchsorted(sorted_rel, jnp.arange(e_loc))
    pos = jnp.arange(t * k) - starts[sorted_rel]
    keep = (sorted_rel < e_loc) & (pos < cap)
    slot = jnp.where(keep, jnp.minimum(pos, cap), cap)     # cap = dump row
    srel = jnp.minimum(sorted_rel, e_loc - 1)
    tok_idx = order // k

    buf = jnp.zeros((e_loc, cap + 1, d), xf.dtype)
    buf = buf.at[jnp.where(keep, srel, 0),
                 slot].set(jnp.where(keep[:, None], xf[tok_idx], 0),
                           mode="drop")
    h = jax.nn.silu(jnp.einsum("ecd,edf->ecf", buf, w_gate)) \
        * jnp.einsum("ecd,edf->ecf", buf, w_up)
    out_slots = jnp.einsum("ecf,efd->ecd", h, w_down)

    gathered = out_slots[srel, slot] * keep[:, None].astype(xf.dtype)
    contrib = jnp.zeros((t * k, d), xf.dtype).at[order].set(gathered)
    y = jnp.sum(contrib.reshape(t, k, d)
                * w_topk[..., None].astype(xf.dtype), axis=1)
    return y


def _expert_apply(xf, rel_e, w_gate, w_up, w_down, cap: int):
    """FFN for tokens already labeled with LOCAL expert ids.

    xf: (M, d); rel_e: (M,) in [0, e_loc] (e_loc = invalid sentinel).
    Returns (M, d); invalid rows produce zeros. Sort-based capacity
    dispatch identical to the source-side path.
    """
    m, d = xf.shape
    e_loc = w_gate.shape[0]
    order = jnp.argsort(rel_e, stable=True)
    sorted_rel = rel_e[order]
    starts = jnp.searchsorted(sorted_rel, jnp.arange(e_loc))
    pos = jnp.arange(m) - starts[sorted_rel]
    keep = (sorted_rel < e_loc) & (pos < cap)
    slot = jnp.where(keep, jnp.minimum(pos, cap), cap)
    srel = jnp.minimum(sorted_rel, e_loc - 1)
    buf = jnp.zeros((e_loc, cap + 1, d), xf.dtype)
    buf = buf.at[jnp.where(keep, srel, 0),
                 slot].set(jnp.where(keep[:, None], xf[order], 0),
                           mode="drop")
    h = jax.nn.silu(jnp.einsum("ecd,edf->ecf", buf, w_gate)) \
        * jnp.einsum("ecd,edf->ecf", buf, w_up)
    out_slots = jnp.einsum("ecf,efd->ecd", h, w_down)
    gathered = out_slots[srel, slot] * keep[:, None].astype(xf.dtype)
    return jnp.zeros((m, d), xf.dtype).at[order].set(gathered)


def _a2a_routed(x_loc, router, wg, wu, wd, *, cfg: ModelConfig, k: int,
                e_total: int, model_axis: str = "model"):
    """All-to-all expert parallelism (§Perf C3) — runs inside shard_map.

    x_loc: this (data × model) shard's SEQUENCE SLICE (B_loc, S/Pm, d) —
    composes with the sequence-parallel residual, so tokens are never
    replicated over the model axis. Each shard routes its own tokens,
    buckets them by destination expert shard, all-to-alls the buckets to
    the expert owners, computes, and reverses the a2a; weights are applied
    at the source in the combine. Wire = 2 × (t_mini·k·cf·d) bytes per
    shard instead of per-layer full-activation psums.
    """
    my = jax.lax.axis_index(model_axis)
    bl, sl, d = x_loc.shape
    t = bl * sl
    xf = x_loc.reshape(t, d)
    e_loc = wg.shape[0]
    # static model-axis extent (jax.lax.axis_size compat): experts are
    # sharded over the model axis, so Pm = E_pad / E_loc
    pm = router.shape[1] // e_loc

    logits = xf.astype(jnp.float32) @ router               # (t, E_pad)
    logits = jnp.where(jnp.arange(logits.shape[-1]) < e_total, logits,
                       -1e30)
    probs = jax.nn.softmax(logits, axis=-1)
    w_topk, sel = jax.lax.top_k(probs, k)
    w_topk = (w_topk / jnp.sum(w_topk, -1, keepdims=True)).reshape(-1)

    flat_e = sel.reshape(-1)                               # (t·k,)
    dest = flat_e // e_loc                                 # owning shard
    cap_s = max(int(cfg.capacity_factor * t * k / pm), 8)  # per-destination
    order = jnp.argsort(dest, stable=True)
    sorted_dest = dest[order]
    starts = jnp.searchsorted(sorted_dest, jnp.arange(pm))
    pos = jnp.arange(t * k) - starts[sorted_dest]
    keep = pos < cap_s
    slot = jnp.where(keep, jnp.minimum(pos, cap_s), cap_s)
    tok_idx = order // k

    send_x = jnp.zeros((pm, cap_s + 1, d), xf.dtype)
    send_x = send_x.at[sorted_dest, slot].set(
        jnp.where(keep[:, None], xf[tok_idx], 0), mode="drop")
    send_e = jnp.full((pm, cap_s + 1), e_loc, jnp.int32)   # sentinel
    send_e = send_e.at[sorted_dest, slot].set(
        jnp.where(keep, flat_e[order] % e_loc, e_loc), mode="drop")
    # source-side bookkeeping for the combine (stays local)
    src_asn = jnp.full((pm, cap_s + 1), t * k, jnp.int32)  # flat asn index
    src_asn = src_asn.at[sorted_dest, slot].set(
        jnp.where(keep, order, t * k), mode="drop")

    recv_x = jax.lax.all_to_all(send_x[:, :cap_s], model_axis, 0, 0,
                                tiled=False)
    recv_e = jax.lax.all_to_all(send_e[:, :cap_s], model_axis, 0, 0,
                                tiled=False)
    cap2 = max(int(cfg.capacity_factor * pm * cap_s / max(e_loc, 1)), 8)
    out = _expert_apply(recv_x.reshape(pm * cap_s, d),
                        recv_e.reshape(pm * cap_s), wg, wu, wd, cap2)
    back = jax.lax.all_to_all(out.reshape(pm, cap_s, d), model_axis, 0, 0,
                              tiled=False)                 # (pm, cap_s, d)
    # combine at the source: y[token] += weight(asn) · result(slot)
    asn = src_asn[:, :cap_s].reshape(-1)                   # (pm·cap_s,)
    w_asn = jnp.where(asn < t * k, w_topk[jnp.minimum(asn, t * k - 1)],
                      0.0).astype(xf.dtype)
    tok_of_asn = jnp.minimum(asn, t * k - 1) // k
    y = jnp.zeros((t, d), xf.dtype).at[tok_of_asn].add(
        back.reshape(-1, d) * w_asn[:, None], mode="drop")
    return y.reshape(bl, sl, d)


def moe_ffn(p: dict, x: jax.Array, cfg: ModelConfig) -> jax.Array:
    """Routed FFN. x: (B, S, d) → (B, S, d).

    With mesh rules active the dispatch runs under shard_map in one of two
    schemes (EXPERIMENTS.md §Perf C1/C3):
      * a2a expert parallelism (default when seq divides the model axis):
        tokens stay sequence-sharded; buckets all-to-all to expert owners —
        wire ∝ routed tokens, not activations;
      * replicated-activation EP (fallback): each model shard processes all
        of its data shard's tokens for its expert slice, psum combine.
    Either way the argsort/scatter chain stays LOCAL — GSPMD otherwise
    replicates the global (T·k, d) dispatch on every device (measured
    113–530 GiB/chip), the skewed-workload-goes-global failure the paper's
    §V-A balance work avoids.
    """
    from repro.runtime.sharding import batch_axes, current_rules

    b, s, d = x.shape
    e, k = cfg.n_experts, cfg.moe_top_k
    rules = current_rules()
    e_pad = cfg.padded_experts          # stacks are padded to shard evenly
    use_smap = (rules is not None
                and e_pad % rules.mesh.shape.get("model", 1) == 0)
    if use_smap:
        mesh = rules.mesh
        daxes = batch_axes(mesh)
        pm = mesh.shape["model"]
        from jax.sharding import PartitionSpec as P
        ep_policy = getattr(rules, "policy", "tp") == "ep"
        if ep_policy and pm > 1 and b % (len(mesh.devices.reshape(-1))
                                         // 1) == 0:
            # §Perf C4: batch sharded over ALL axes; only the a2a moves data
            import functools as _ft
            xs = P(daxes + ("model",), None, None)
            y = _shard_map(
                _ft.partial(_a2a_routed, cfg=cfg, k=k, e_total=e),
                mesh=mesh,
                in_specs=(xs, P(), P("model", None, None),
                          P("model", None, None), P("model", None, None)),
                out_specs=xs, check_vma=False,
            )(x, p["router"], p["w_gate"], p["w_up"], p["w_down"])
            if "shared" in p:
                y = y + layers.mlp(p["shared"], x.reshape(b * s, d),
                                   act="silu").reshape(b, s, d)
            return y
        use_a2a = pm > 1 and s % pm == 0 and (s // pm) >= 1
        if use_a2a:                       # §Perf C3: a2a expert parallelism
            import functools as _ft
            xs = P(daxes, "model", None)
            y = _shard_map(
                _ft.partial(_a2a_routed, cfg=cfg, k=k, e_total=e),
                mesh=mesh,
                in_specs=(xs, P(), P("model", None, None),
                          P("model", None, None), P("model", None, None)),
                out_specs=xs, check_vma=False,
            )(x, p["router"], p["w_gate"], p["w_up"], p["w_down"])
        else:                             # replicated-activation EP (C1)
            n_data = 1
            for a in daxes:
                n_data *= mesh.shape[a]
            t_loc = max(b // max(n_data, 1), 1) * s
            cap = _capacity(cfg, t_loc, k, e)

            def routed(x_blk, router, wg, wu, wd):
                my = jax.lax.axis_index("model")
                e_loc = wg.shape[0]
                bl, sl, _ = x_blk.shape
                y = _dispatch_compute_combine(
                    x_blk.reshape(bl * sl, d), router, wg, wu, wd,
                    cap=cap, k=k, e_base=my * e_loc, e_total=e)
                return jax.lax.psum(y.reshape(bl, sl, d), "model")

            xs = P(daxes, None, None)
            y = _shard_map(
                routed, mesh=mesh,
                in_specs=(xs, P(), P("model", None, None),
                          P("model", None, None), P("model", None, None)),
                out_specs=xs, check_vma=False,
            )(x, p["router"], p["w_gate"], p["w_up"], p["w_down"])
    else:
        t = b * s
        cap = _capacity(cfg, t, k, e)
        y = _dispatch_compute_combine(
            x.reshape(t, d), p["router"], p["w_gate"], p["w_up"],
            p["w_down"], cap=cap, k=k, e_base=jnp.int32(0),
            e_total=e).reshape(b, s, d)
    if "shared" in p:
        y = y + layers.mlp(p["shared"], x.reshape(b * s, d),
                           act="silu").reshape(b, s, d)
    return y


def router_load_stats(p: dict, x: jax.Array, cfg: ModelConfig) -> dict:
    """Instrumentation: per-expert load + overflow fraction (Fig-15 analogue
    for the MoE transfer of the paper's balance study)."""
    b, s, d = x.shape
    t = b * s
    logits = x.reshape(t, d).astype(jnp.float32) @ p["router"]
    logits = jnp.where(jnp.arange(logits.shape[-1]) < cfg.n_experts,
                       logits, -1e30)
    _, sel = jax.lax.top_k(jax.nn.softmax(logits, -1), cfg.moe_top_k)
    counts = jnp.bincount(sel.reshape(-1), length=cfg.n_experts)
    cap = _capacity(cfg, t, cfg.moe_top_k, cfg.n_experts)
    overflow = jnp.sum(jnp.maximum(counts - cap, 0)) / (t * cfg.moe_top_k)
    return {"counts": counts, "capacity": cap, "overflow_frac": overflow,
            "imbalance": counts.max() / jnp.maximum(counts.mean(), 1e-9)}
