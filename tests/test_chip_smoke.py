"""chip_smoke.py: its refusals, and its one-chip path at a toy size.

The script itself runs only on a TPU. Here its phases run on the CPU
(kernels interpreted) with the device check and the memory check
stubbed in the test, at a corpus and K small enough for interpret mode.
"""

from __future__ import annotations

import importlib.util
import json
import os
import pathlib
import shutil
import subprocess
import sys

import numpy as np

ROOT = pathlib.Path(__file__).resolve().parents[1]
SCRIPT = ROOT / "chip_smoke.py"


def _run(cwd, script, **env):
    base = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    return subprocess.run([sys.executable, str(script)], cwd=cwd,
                          capture_output=True, text=True, timeout=300,
                          env={**base, **env})


def _no_result(proc) -> bool:
    lines = proc.stdout.strip().splitlines()
    return proc.returncode != 0 and not (lines and '"ok"' in lines[-1])


def test_refuses_without_a_tpu():
    proc = _run(ROOT, SCRIPT, JAX_PLATFORMS="cpu")
    assert _no_result(proc), proc.stdout
    assert "no TPU" in proc.stderr


def test_refuses_outside_the_repo(tmp_path):
    alone = tmp_path / "chip_smoke.py"
    shutil.copy(SCRIPT, alone)
    proc = _run(tmp_path, alone, JAX_PLATFORMS="cpu")
    assert _no_result(proc), proc.stdout


def _load_smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke", SCRIPT)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_one_chip_path_at_toy_size(monkeypatch, capsys):
    smoke = _load_smoke()
    for name, value in (("N_WORDS", 300), ("N_DOCS", 60), ("N_TOKENS", 2400),
                        ("N_TOPICS", 16), ("N_HELDOUT", 4), ("N_ITERS", 2),
                        ("KERNEL_TOKENS", 512), ("KERNEL_WINDOW", 64),
                        ("DOC_FRACTION", 1.0)):
        monkeypatch.setattr(smoke, name, value)
    monkeypatch.setattr(smoke, "device_phase", lambda want: {
        "platform": "cpu", "kind": "cpu", "count": want})
    monkeypatch.setattr(smoke, "fits_phase", lambda need, devices: None)
    monkeypatch.setattr(smoke, "mem_stat", lambda dev, key="": 0)
    seen = []
    real_evidence = smoke.kernel_evidence

    def cpu_evidence(engine):
        # on the CPU the kernels run interpreted, so no Mosaic call exists;
        # the script's own check wants the chip's answer
        interpret, n_calls = real_evidence(engine)
        seen.append((interpret, n_calls))
        return False, 1

    monkeypatch.setattr(smoke, "kernel_evidence", cpu_evidence)
    device = smoke.one_chip(smoke.CompileClock())
    assert device == {"platform": "cpu", "kind": "cpu", "count": 1}
    assert seen == [(True, 0), (True, 0)]
    out = capsys.readouterr().out
    lines = [json.loads(ln[len("[train] "):]) for ln in out.splitlines()
             if ln.startswith("[train] {")]
    assert [ln["config"] for ln in lines] == ["default", "pallas_fused",
                                              "hybrid_sparse"]
    for ln in lines:
        assert ln["conserved"] and ln["backend"] == "single"
        assert np.isfinite([ln["llpt_iter0"], ln["llpt_iter2"],
                            *ln["llpt_fit"]]).all()
    assert "[serve] docs=4 theta=(4, 16)" in out


_FOUR_CHIPS = r"""
import importlib.util, json, os, sys
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
spec = importlib.util.spec_from_file_location("chip_smoke", sys.argv[1])
smoke = importlib.util.module_from_spec(spec)
spec.loader.exec_module(smoke)
smoke.N_WORDS, smoke.N_DOCS, smoke.N_TOKENS = 300, 80, 3200
smoke.N_TOPICS, smoke.N_ITERS, smoke.DOC_FRACTION = 16, 2, 1.0
smoke.LLPT_TOL = 0.05          # a toy corpus: 3,200 tokens, not 1e8
smoke.device_phase = lambda want: {"platform": "cpu", "kind": "cpu",
                                   "count": want}
smoke.fits_phase = lambda need, devices: None
smoke.mem_stat = lambda dev, key="": 1
print(json.dumps(smoke.four_chips(smoke.CompileClock())))
"""


def test_four_chip_path_on_forged_devices():
    proc = subprocess.run(
        [sys.executable, "-c", _FOUR_CHIPS, str(SCRIPT)], cwd=ROOT,
        capture_output=True, text=True, timeout=600,
        env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert proc.returncode == 0, proc.stderr[-4000:]
    out = proc.stdout
    assert json.loads(out.strip().splitlines()[-1])["count"] == 4
    assert "D shards on 4 chips" in out
    assert '"config": "distributed_4x1", "backend": "distributed"' in out
