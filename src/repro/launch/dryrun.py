import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
# (must be the very first lines — jax locks the device count on first init;
#  tests may shrink the forged count via REPRO_DRYRUN_DEVICES before import)
if os.environ.get("REPRO_DRYRUN_DEVICES"):
    os.environ["XLA_FLAGS"] = ("--xla_force_host_platform_device_count="
                               + os.environ["REPRO_DRYRUN_DEVICES"])

"""Multi-pod dry-run: lower + compile every (arch × shape × mesh) cell.

For each cell this builds the production mesh (16×16 single-pod or 2×16×16
multi-pod) over forged host devices, lowers the real train/prefill/serve
step with ShapeDtypeStruct inputs (zero allocation), compiles, and records
memory_analysis + cost_analysis + the HLO-parsed collective bytes — the
inputs to EXPERIMENTS.md §Dry-run/§Roofline.

One cell per invocation (subprocess isolation keeps a 62-layer compile from
taking the whole sweep down); drive sweeps with benchmarks/run.py.

Usage:
  python -m repro.launch.dryrun --arch deepseek-moe-16b --shape train_4k \
      --mesh multi --out results/cell.json
  python -m repro.launch.dryrun --list
  python -m repro.launch.dryrun --lda --mesh single   # the paper's own model
"""

import argparse
import json
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs import REGISTRY, SHAPES, shape_applicable
from repro.launch.mesh import make_production_mesh
from repro.models.registry import get_model, input_specs
from repro.roofline.analysis import HW, roofline_terms, summarize_memory
from repro.runtime.sharding import batch_axes, safe_spec
from repro.train import partition
from repro.train.serve_step import (make_prefill_step, make_serve_step,
                                    serve_state_shardings)
from repro.train.train_step import (batch_shardings, default_microbatches,
                                    make_train_step, train_state_specs)


from repro.roofline.flops_model import analytic_cell


def lower_cell(arch: str, shape_name: str, multi_pod: bool,
               n_micro: int | None = None, policy: str = "tp",
               remat: str | None = None, seq_parallel: bool = True,
               rs_per_micro: bool = True) -> dict:
    import dataclasses as _dc
    cfg = REGISTRY[arch]
    overrides = {}
    if remat is not None:
        overrides["remat"] = remat
    if not seq_parallel:
        overrides["seq_parallel"] = False
    if overrides:
        cfg = _dc.replace(cfg, **overrides)
    shape = SHAPES[shape_name]
    ok, why = shape_applicable(cfg, shape)
    if not ok:
        return {"arch": arch, "shape": shape_name,
                "mesh": "multi" if multi_pod else "single",
                "status": "skipped", "reason": why}
    mesh = make_production_mesh(multi_pod=multi_pod)
    api = get_model(cfg)
    t0 = time.time()
    params_shape = jax.eval_shape(api.init, jax.random.PRNGKey(0))
    n_params = sum(int(np.prod(x.shape))
                   for x in jax.tree.leaves(params_shape))

    if shape.kind == "train":
        micro = n_micro or default_microbatches(cfg, shape, mesh, policy)
        step, _ = make_train_step(api, mesh, micro, policy=policy,
                                  rs_per_micro=rs_per_micro)
        state_sh = train_state_specs(mesh, params_shape, policy)
        opt_shape = jax.eval_shape(
            lambda p: __import__("repro.train.optimizer",
                                 fromlist=["init_opt_state"]
                                 ).init_opt_state(p), params_shape)
        state_shape = {"params": params_shape, "opt": opt_shape,
                       "step": jax.ShapeDtypeStruct((), jnp.int32)}
        bspec = input_specs(cfg, shape.seq_len, shape.global_batch, "train")
        bshard = batch_shardings(mesh, bspec, policy)
        rep = NamedSharding(mesh, P())
        metric_sh = {"grad_norm": rep, "lr": rep, "loss": rep}
        lowered = jax.jit(step, in_shardings=(state_sh, bshard),
                          out_shardings=(state_sh, metric_sh),
                          donate_argnums=(0,)
                          ).lower(state_shape, bspec)
        extra = {"n_microbatches": micro}
    elif shape.kind == "prefill":
        step = make_prefill_step(api, mesh)
        p_shard = partition.zero1_shardings(mesh, params_shape)
        bspec = input_specs(cfg, shape.seq_len, shape.global_batch,
                            "prefill")
        bshard = batch_shardings(mesh, bspec)
        key = "frames" if cfg.is_encoder_decoder else "inputs"
        out_sh = NamedSharding(mesh, safe_spec(
            mesh, (shape.global_batch, cfg.padded_vocab),
            [batch_axes(mesh), "model"]))
        lowered = jax.jit(step, in_shardings=(p_shard, bshard[key]),
                          out_shardings=out_sh
                          ).lower(params_shape, bspec[key])
        extra = {}
    else:                                            # decode
        b = shape.global_batch
        if cfg.is_encoder_decoder:
            pshape, cshape, p_shard, c_shard = serve_state_shardings(
                api, mesh, b, shape.seq_len, enc_len=shape.seq_len)
        else:
            pshape, cshape, p_shard, c_shard = serve_state_shardings(
                api, mesh, b, shape.seq_len)
        step = make_serve_step(api, mesh)
        bspec = input_specs(cfg, shape.seq_len, b, "decode")
        bshard = batch_shardings(mesh, bspec)
        logits_sh = NamedSharding(mesh, safe_spec(
            mesh, (b, cfg.padded_vocab), [batch_axes(mesh), "model"]))
        lowered = jax.jit(step, in_shardings=(p_shard, c_shard,
                                              bshard["tokens"]),
                          out_shardings=(logits_sh, c_shard),
                          donate_argnums=(1,)
                          ).lower(pshape, cshape, bspec["tokens"])
        extra = {}

    compiled = lowered.compile()
    t_compile = time.time() - t0
    mem = summarize_memory(compiled.memory_analysis())
    text = compiled.as_text()
    rf = roofline_terms(compiled, mesh.devices.size, hlo_text=text)
    hw = HW()
    cost = analytic_cell(cfg, shape, dict(mesh.shape),
                         n_micro=extra.get("n_microbatches", 1),
                         policy=policy, rs_per_micro=rs_per_micro)
    terms = cost.terms(hw)
    dominant = max(terms, key=terms.get)
    total = sum(terms.values())
    result = {
        "arch": arch, "shape": shape_name,
        "mesh": "multi" if multi_pod else "single",
        "mesh_shape": dict(mesh.shape),
        "status": "ok",
        "n_params": n_params,
        "n_active_params": cfg.active_param_count(),
        "compile_seconds": round(t_compile, 1),
        "memory": mem,
        "fits_hbm": mem["peak_bytes_estimate"] < hw.hbm_bytes,
        # raw HLO counters (scan bodies counted once — see EXPERIMENTS.md)
        "roofline_hlo_raw": rf,
        # corrected analytic model (the headline §Roofline numbers)
        "roofline": {
            **terms,
            "dominant": dominant,
            "flops": cost.flops,
            "hbm_bytes": cost.hbm_bytes,
            "wire_bytes": cost.wire_bytes,
            "model_flops": cost.model_flops,
            "useful_compute_ratio": (cost.model_flops / cost.flops
                                     if cost.flops else 0.0),
            "step_time_bound_s": max(terms.values()),
            # roofline fraction = useful-compute time / step time
            "mfu_bound_overlap": (cost.model_flops / hw.peak_flops
                                  / max(terms.values())) if total else 0.0,
            "mfu_no_overlap": (cost.model_flops / hw.peak_flops / total)
                              if total else 0.0,
            "detail": cost.detail,
        },
        **extra,
    }
    return result


def lower_lda(multi_pod: bool, n_topics: int = 1024, v: int = 65_536,
              n_loc: int = 262_144, m_loc: int = 8_192) -> dict:
    """Dry-run the paper's own model: the distributed EZLDA step on the
    production mesh (UMBC-scale shard sizes: V=64Ki words, 256Ki tokens and
    8Ki docs per data shard, K topics sharded over 'model')."""
    from repro.lda.distributed import DistLDAState, _dist_step
    from repro.lda.model import LDAConfig
    import functools
    from repro.core.three_branch import ThreeBranchStats

    mesh = make_production_mesh(multi_pod=multi_pod)
    daxes = batch_axes(mesh)
    n_data = int(np.prod([mesh.shape[a] for a in daxes]))
    cfg = LDAConfig(n_topics=n_topics)
    t0 = time.time()
    f = jax.ShapeDtypeStruct
    tok = f((n_data, n_loc), jnp.int32)
    state_shape = DistLDAState(
        topics=f((n_data, n_loc), jnp.int32),
        D=f((n_data, m_loc, n_topics), jnp.int32),
        W=f((v, n_topics), jnp.int32),
        key=jax.eval_shape(lambda: jax.random.PRNGKey(0)),
        iteration=f((), jnp.int32))
    tok_spec = P(daxes)
    state_specs = DistLDAState(topics=tok_spec, D=P(daxes, None, "model"),
                               W=P(None, "model"), key=P(), iteration=P())
    stats_spec = ThreeBranchStats(*[P()] * len(ThreeBranchStats._fields))
    step = functools.partial(
        _dist_step, cfg=cfg, data_axes=daxes, model_axis="model",
        n_words=v, m_local=m_loc, g=cfg.g)
    smapped = jax.shard_map(
        step, mesh=mesh,
        in_specs=(tok_spec, tok_spec, tok_spec, state_specs),
        out_specs=(state_specs, stats_spec), check_vma=False)
    sh = lambda s: NamedSharding(mesh, s)
    lowered = jax.jit(
        smapped,
        in_shardings=(sh(tok_spec), sh(tok_spec), sh(tok_spec),
                      jax.tree.map(sh, state_specs)),
        out_shardings=(jax.tree.map(sh, state_specs),
                       jax.tree.map(sh, stats_spec)),
    ).lower(tok, tok, tok, state_shape)
    compiled = lowered.compile()
    t_compile = time.time() - t0
    mem = summarize_memory(compiled.memory_analysis())
    rf = roofline_terms(compiled, mesh.devices.size)
    hw = HW()
    return {
        "arch": f"lda-ezlda-K{n_topics}", "shape": f"tokens{n_loc}pershard",
        "mesh": "multi" if multi_pod else "single",
        "mesh_shape": dict(mesh.shape), "status": "ok",
        "compile_seconds": round(t_compile, 1),
        "memory": mem, "fits_hbm": mem["peak_bytes_estimate"] < hw.hbm_bytes,
        "roofline": rf,
    }


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=sorted(REGISTRY))
    ap.add_argument("--shape", choices=sorted(SHAPES))
    ap.add_argument("--mesh", choices=["single", "multi"], default="single")
    ap.add_argument("--lda", action="store_true",
                    help="dry-run the paper's own distributed LDA step")
    ap.add_argument("--topics", type=int, default=1024)
    ap.add_argument("--microbatches", type=int, default=None)
    ap.add_argument("--policy", choices=["tp", "dp", "fsdp", "ep"], default="tp",
                    help="dp: repurpose the model axis as data parallelism"
                         " (small models; EXPERIMENTS.md §Perf)")
    ap.add_argument("--remat", choices=["full", "none"], default=None)
    ap.add_argument("--no-sp", action="store_true",
                    help="disable sequence-parallel residual (§Perf it.2)")
    ap.add_argument("--rs-once", action="store_true",
                    help="single step-end grad reduce-scatter (§Perf it.3)")
    ap.add_argument("--out", default=None)
    ap.add_argument("--list", action="store_true")
    args = ap.parse_args(argv)

    if args.list:
        for a in sorted(REGISTRY):
            for s in SHAPES:
                ok, why = shape_applicable(REGISTRY[a], SHAPES[s])
                print(f"{a:24s} {s:12s} {'run' if ok else 'SKIP: ' + why}")
        return 0

    if args.lda:
        result = lower_lda(args.mesh == "multi", n_topics=args.topics)
    else:
        if not (args.arch and args.shape):
            ap.error("--arch and --shape required (or --list/--lda)")
        result = lower_cell(args.arch, args.shape, args.mesh == "multi",
                            n_micro=args.microbatches, policy=args.policy,
                            remat=args.remat, seq_parallel=not args.no_sp,
                            rs_per_micro=not args.rs_once)

    print(json.dumps(result, indent=2, default=float))
    if args.out:
        result.setdefault("policy", args.policy)
        os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
        with open(args.out, "w") as fh:
            json.dump(result, fh, indent=2, default=float)
    return 0 if result["status"] in ("ok", "skipped") else 1


if __name__ == "__main__":
    sys.exit(main())
