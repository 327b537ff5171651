"""BENCHMARK.json keeps to its contract, and every part of a cell is found
by name from files: adding a cell takes files and an entry, no edit."""

import json
import re
import shutil

import pytest

from bench import harness

ROOT = harness.ROOT
SPEC = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


def _line(s):
    return isinstance(s, str) and 1 <= len(s) <= 200 and "\n" not in s \
        and "\t" not in s


def test_top_level():
    assert set(SPEC) == {"command", "paths", "run_seconds", "configs",
                         "workloads", "end_to_end", "per_layer"}
    assert SPEC["paths"] == ["bench"]
    assert SPEC["command"] == ["python3", "bench/run.py"]
    assert 1 <= SPEC["run_seconds"] <= 51
    assert len((ROOT / "BENCHMARK.json").read_bytes()) <= 64 * 1024


def test_configs():
    used = {w["config"] for w in SPEC["workloads"]}
    for c in SPEC["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"]) and c["name"] in used
        assert _line(c["source"]) and _line(c["why"])
        assert c["file"].startswith("bench/")
        body = json.loads((ROOT / c["file"]).read_text())
        assert body["reduced"] == c["reduced"]
        for k in c["reduced"]:
            assert NAME.match(k)
            assert body[k] != body["published"][k]
        for k, v in body["published"].items():
            if k not in c["reduced"] and k in body:
                assert body[k] == v, k


def test_workloads():
    names = [w["name"] for w in SPEC["workloads"]]
    assert len(names) == len(set(names))
    pairs = {(w["config"], w["traffic"]) for w in SPEC["workloads"]}
    assert len(pairs) == len(names)
    for w in SPEC["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["name"]) and NAME.match(w["traffic"])
        assert w["chips"] in (1, 4) and _line(w["why"])


def test_metrics():
    e2e = {m["name"] for m in SPEC["end_to_end"]}
    assert "setup_s" in e2e
    cells = {w["name"] for w in SPEC["workloads"]}
    for m in SPEC["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound",
                                          "source"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    layers = {}
    for m in SPEC["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source",
                                          "layer", "moves"}
        assert m["moves"] in e2e and _line(m["layer"])
        assert set(m.get("workloads", cells)) <= cells
        assert (harness.BENCH / "metrics" / f"{m['name']}.py").exists()
        layers.setdefault(m["layer"], m["layer"])
    for m in SPEC["end_to_end"] + SPEC["per_layer"]:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")
    for cell in cells:
        reported = [m for m in SPEC["per_layer"]
                    if cell in m.get("workloads", [cell])]
        assert reported, cell


@pytest.mark.parametrize("name", [w["name"] for w in SPEC["workloads"]])
def test_cells_load_by_name(name):
    cell = harness.load_cell(name)
    assert cell.config["name"] == next(
        w["config"] for w in SPEC["workloads"] if w["name"] == name)
    assert cell.traffic["init"] in ("random", "planted")
    assert float(cell.cell["iter_ref_s"]) > 0
    assert set(cell.cell["limits"]) == {"topic_mismatch", "window_mismatch",
                                        "count_gap", "llpt_gap",
                                        "compiles_in_window"}
    assert set(cell.readers) == {m["name"] for m in cell.per_layer}
    assert {m["name"] for m in cell.end_to_end} == {"train_tokens_per_s",
                                                   "setup_s"}
    # the window's one ``fit`` call holds a transition the check replays
    assert harness.window_iters(cell, SPEC["run_seconds"]) >= 2


def test_a_cell_added_from_files_alone(tmp_path):
    """A new configuration, traffic mix, cell and per-layer metric: only
    new files and new entries, and the harness finds each by name."""
    shutil.copytree(harness.BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("tests", "__pycache__"))
    spec = json.loads(json.dumps(SPEC))
    bench = tmp_path / "bench"
    cfg = json.loads((bench / "configs" / "nytimes-k1k.json").read_text())
    cfg.update(name="umbc-k1k", n_words=200000, mean_doc_len=33)
    (bench / "configs" / "umbc-k1k.json").write_text(json.dumps(cfg))
    (bench / "traffic" / "midway.json").write_text(json.dumps(
        {"name": "midway", "init": "planted", "why": "a job halfway through"}))
    (bench / "cells" / "umbc-k1k.midway.json").write_text(json.dumps(
        {"iter_ref_s": 5.0, "limits": {"topic_mismatch": 1e-5,
                                       "window_mismatch": 1e-5,
                                       "count_gap": 0, "llpt_gap": 1e-5,
                                       "compiles_in_window": 0}}))
    (bench / "metrics" / "iters.train.py").write_text(
        "def read(ctx):\n    return float(len(ctx.get('stats', [])))\n")
    spec["configs"].append({"name": "umbc-k1k", "source": "x",
                            "file": "bench/configs/umbc-k1k.json",
                            "reduced": ["n_docs"], "why": "x"})
    spec["workloads"].append({"name": "umbc-k1k.midway", "config": "umbc-k1k",
                              "traffic": "midway", "chips": 1, "why": "x"})
    spec["per_layer"].append({"name": "iters.train", "unit": "count",
                              "better": "higher", "source": "program_counter",
                              "layer": "x", "moves": "train_tokens_per_s",
                              "workloads": ["umbc-k1k.midway"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(spec))
    cell = harness.load_cell("umbc-k1k.midway", tmp_path, bench)
    assert cell.config["n_words"] == 200000
    assert cell.traffic["init"] == "planted"
    assert harness.window_iters(cell, 30) == 6
    assert list(cell.readers) == ["iters.train"]
    assert cell.readers["iters.train"]({"stats": [{}, {}]}) == 2.0
    old = harness.load_cell("nytimes-k1k.cold", tmp_path, bench)
    assert "iters.train" not in old.readers


def test_unknown_cell_is_an_error():
    with pytest.raises(KeyError):
        harness.load_cell("no-such.cell")
