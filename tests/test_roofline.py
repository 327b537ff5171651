"""Roofline tooling tests: HLO shape/collective parsing + the analytic cost
model calibrated against fully-unrolled HLO (where HloCostAnalysis is exact)."""

import subprocess
import sys
import textwrap

import pytest

from repro.roofline.analysis import (collective_bytes, parse_shape_bytes)


def test_parse_shape_bytes():
    assert parse_shape_bytes("f32[16,128]") == 16 * 128 * 4
    assert parse_shape_bytes("bf16[8]{0}") == 16
    assert parse_shape_bytes("pred[]") == 1
    assert parse_shape_bytes("s32[2,2]{1,0:T(2,2)}") == 16
    # async pair: take the destination buffer (last element)
    assert parse_shape_bytes("(f32[4]{0}, f32[16]{0})") == 64


def test_collective_bytes_ring_model():
    hlo = """
  %ar = f32[1024]{0} all-reduce(f32[1024]{0} %x), replica_groups={{0,1,2,3}}
  %ag = bf16[64,16]{1,0} all-gather(bf16[16,16]{1,0} %y), replica_groups={{0,1,2,3}}
  %rs = f32[256]{0} reduce-scatter(f32[1024]{0} %z), replica_groups={{0,1,2,3}}
  %done = f32[8]{0} all-reduce-done(f32[8]{0} %h)
    """
    out = collective_bytes(hlo, 4)
    assert out["all-reduce"] == pytest.approx(2 * 4096 * 3 / 4)
    assert out["all-gather"] == pytest.approx(64 * 16 * 2 * 3 / 4)
    assert out["reduce-scatter"] == pytest.approx(256 * 4 * 3)
    assert out["count"] == 3          # -done not double counted


def test_collective_bytes_iota_groups():
    hlo = "%ar = f32[100]{0} all-reduce(f32[100]{0} %x), replica_groups=[2,8]<=[16]"
    out = collective_bytes(hlo, 16)
    assert out["all-reduce"] == pytest.approx(2 * 400 * 7 / 8)


@pytest.mark.slow
def test_analytic_model_calibration():
    """Analytic FLOPs within 15% of fully-unrolled HLO for dense + ssm.

    (Unrolled ⇒ no while loops ⇒ HloCostAnalysis counts everything; this is
    the ground truth the rolled dry-run's analytic numbers stand on.)
    """
    code = textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=16"
        import sys; sys.path.insert(0, "src")
        import dataclasses, jax, jax.numpy as jnp
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.configs import REGISTRY, SHAPES
        from repro.models.registry import get_model, input_specs
        from repro.roofline.analysis import roofline_terms
        from repro.roofline.flops_model import analytic_cell
        from repro.train.train_step import (make_train_step,
            train_state_specs, batch_shardings)
        from repro.train.optimizer import init_opt_state
        from repro.runtime.compat import make_mesh
        mesh = make_mesh((4, 4), ("data", "model"))
        for arch in ("qwen1.5-0.5b", "mamba2-370m"):
            cfg = dataclasses.replace(REGISTRY[arch], scan_unroll=True,
                                      n_layers=4)
            api = get_model(cfg)
            shape = dataclasses.replace(SHAPES["train_4k"], seq_len=512,
                                        global_batch=8)
            pshape = jax.eval_shape(api.init, jax.random.PRNGKey(0))
            step, _ = make_train_step(api, mesh, n_micro=1)
            st_sh = train_state_specs(mesh, pshape)
            o_sh = jax.eval_shape(init_opt_state, pshape)
            st = {"params": pshape, "opt": o_sh,
                  "step": jax.ShapeDtypeStruct((), jnp.int32)}
            bspec = input_specs(cfg, 512, 8, "train")
            rep = NamedSharding(mesh, P())
            low = jax.jit(step, in_shardings=(st_sh,
                          batch_shardings(mesh, bspec)),
                          out_shardings=(st_sh, {"grad_norm": rep,
                                                 "lr": rep, "loss": rep})
                          ).lower(st, bspec)
            rf = roofline_terms(low.compile(), 16)
            cost = analytic_cell(cfg, shape, {"data": 4, "model": 4},
                                 n_micro=1)
            ratio = rf["hlo_flops"] / cost.flops
            assert 0.85 < ratio < 1.2, (arch, ratio)
            print(arch, round(ratio, 3))
        print("OK")
    """)
    proc = subprocess.run([sys.executable, "-c", code],
                          capture_output=True, text=True, timeout=900)
    assert proc.returncode == 0 and "OK" in proc.stdout, proc.stderr[-3000:]


@pytest.mark.slow
def test_dryrun_cell_end_to_end():
    """The dry-run machinery itself: one real cell on the production 16×16
    mesh (whisper decode — the fastest compile), lowered + compiled +
    analyzed in a subprocess exactly as the sweep runs it."""
    proc = subprocess.run(
        [sys.executable, "-m", "repro.launch.dryrun", "--arch",
         "whisper-base", "--shape", "decode_32k", "--mesh", "multi"],
        capture_output=True, text=True, timeout=900,
        env={**__import__("os").environ, "PYTHONPATH": "src"})
    assert proc.returncode == 0, proc.stderr[-3000:]
    import json
    d = json.loads(proc.stdout)
    assert d["status"] == "ok"
    assert d["mesh_shape"] == {"pod": 2, "data": 16, "model": 16}
    assert d["roofline"]["dominant"] in ("compute_s", "memory_s",
                                         "collective_s")
    assert d["roofline_hlo_raw"]["collectives"]["count"] > 0
