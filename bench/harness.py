"""One run of one benchmark cell: set-up, the measured window, the check.

Everything a cell is made of is found by name from ``BENCHMARK.json``:
its configuration file, ``traffic/<traffic>.json``, ``cells/<cell>.json``
(the reference iteration time that sizes the window, and the limits of the
check) and one reader ``metrics/<metric>.py`` per per-layer metric. Adding
a cell, a configuration, a traffic mix or a metric takes new files and a
``BENCHMARK.json`` entry, never an edit here.

The window drives ``LDAEngine.fit`` with an ``LDAConfig`` that sets only what
the configuration defines (K, α, β); every implementation knob keeps the
engine's default, so a change of what users get by default is measured.
"""

from __future__ import annotations

import dataclasses
import gc
import importlib.util
import json
import pathlib
import shutil
import sys
import time

import numpy as np

from bench import corpus_gen, roofline

BENCH = pathlib.Path(__file__).resolve().parent
ROOT = BENCH.parent
WINDOW_SPAN = "bench.window"
CHECK_TOKENS = 1 << 20        # tokens drawn from the seed to compare per step


def log(*parts) -> None:
    print(*parts, file=sys.stderr, flush=True)


# ---------------------------------------------------------------------------
# the cell, from its files
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    cell: dict
    end_to_end: list
    per_layer: list
    readers: dict


def _load_reader(path: pathlib.Path):
    spec = importlib.util.spec_from_file_location(
        "bench_metric_" + path.stem.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def _reports(metric: dict, cell: str) -> bool:
    return cell in metric.get("workloads", [cell])


def load_cell(name: str, root: pathlib.Path = ROOT,
              bench: pathlib.Path = BENCH) -> Cell:
    spec = json.loads((root / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json: "
                       f"{sorted(cells)}")
    w = cells[name]
    cfg_entry = {c["name"]: c for c in spec["configs"]}[w["config"]]
    per_layer = [m for m in spec["per_layer"] if _reports(m, name)]
    return Cell(
        name=name, chips=int(w["chips"]),
        config=json.loads((root / cfg_entry["file"]).read_text()),
        traffic=json.loads((bench / "traffic" / f"{w['traffic']}.json")
                           .read_text()),
        cell=json.loads((bench / "cells" / f"{name}.json").read_text()),
        end_to_end=[m for m in spec["end_to_end"] if _reports(m, name)],
        per_layer=per_layer,
        readers={m["name"]: _load_reader(bench / "metrics" / f"{m['name']}.py")
                 for m in per_layer})


def window_iters(cell: Cell, seconds: float) -> int:
    """Whole iterations in the window, from the cell's reference iteration
    time (measured once on the chip), never from this run's own speed."""
    return max(1, int(round(seconds / float(cell.cell["iter_ref_s"]))))


# ---------------------------------------------------------------------------
# devices and compiles
# ---------------------------------------------------------------------------

class NoChip(RuntimeError):
    pass


def devices(chips: int, require_tpu: bool) -> dict:
    import jax
    devs = jax.devices()
    log(f"[device] backend={jax.default_backend()} count={len(devs)} "
        f"kind={devs[0].device_kind}")
    if require_tpu and jax.default_backend() != "tpu":
        raise NoChip(f"no TPU: JAX's backend is {jax.default_backend()!r}; "
                     "the benchmark never falls back to another device")
    if require_tpu and len(devs) < chips:
        raise NoChip(f"the cell needs {chips} chips, JAX finds {len(devs)}")
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": chips}


def enable_compile_cache() -> None:
    """JAX's persistent compilation cache where the program keeps it (the
    environment's ``JAX_COMPILATION_CACHE_DIR``, else its fixed directory
    in the checkout); every program is kept, however short its compile."""
    import jax
    from repro.runtime.compile_cache import enable_compile_cache as enable
    enable()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)


class CompileClock:
    """Counts and sums JAX's own lowering and backend-compile events
    (a copy of ``chip_smoke.CompileClock``, with a count)."""

    EVENTS = ("/jax/core/compile/jaxpr_to_mlir_module_duration",
              "/jax/core/compile/backend_compile_duration")

    def __init__(self):
        import jax
        self.seconds = 0.0
        self.lowerings = 0
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event, duration, **_):
        if event in self.EVENTS:
            self.seconds += duration
            self.lowerings += event == self.EVENTS[0]


# ---------------------------------------------------------------------------
# one run
# ---------------------------------------------------------------------------

def make_corpus(cell: Cell, seed: int):
    from repro.lda.corpus import Corpus
    g = corpus_gen.generate(cell.config, seed)
    t0 = time.perf_counter()
    corpus = Corpus(
        word_ids=g.word_ids, doc_ids=g.doc_ids, n_words=g.n_words,
        n_docs=g.n_docs,
        word_offsets=np.concatenate([[0], np.cumsum(g.word_counts)]),
        word_token_counts=g.word_counts, doc_lengths=g.doc_lengths,
        inv_doc_offsets=np.concatenate([[0], np.cumsum(g.doc_lengths)]),
        inv_token_idx=np.argsort(g.doc_ids, kind="stable").astype(np.int64))
    host_s = g.seconds + time.perf_counter() - t0
    log(f"[corpus] {cell.config['name']} docs={g.n_docs} words={g.n_words} "
        f"tokens={g.n_tokens} topics={g.n_topics} host_s={host_s:.3f}")
    return g, corpus


def lda_config(cell: Cell):
    from repro.lda.model import LDAConfig
    c = cell.config
    return LDAConfig(n_topics=int(c["n_topics"]), alpha=float(c["alpha"]),
                     beta=float(c["beta"]))


def warm_payload(g, cell: Cell, seed: int) -> dict:
    rng = np.random.default_rng(np.random.SeedSequence([int(seed) % 2**64, 3]))
    return {"topics_global": g.topics,
            "key": rng.integers(0, 2**32, size=2, dtype=np.uint32),
            "iteration": int(cell.config["assumed"]["warm_start_iteration"])}


def check_sample(n: int, seed: int) -> np.ndarray:
    rng = np.random.default_rng(np.random.SeedSequence([int(seed) % 2**64, 5]))
    return np.unique(rng.integers(0, n, size=min(n, CHECK_TOKENS)))


@dataclasses.dataclass
class Snapshots:
    """What set-up's checked iteration leaves for the check."""
    topics: list          # z_0, z_1 (host, canonical order)
    key0: np.ndarray      # the key that drew z_1
    iteration: int        # the engine's iteration after it
    D: np.ndarray         # the program's counts after it
    W: np.ndarray
    seconds: float = 0.0  # host time spent taking them (not set-up)


def checked_step(engine, traffic: dict, payload: dict | None) -> Snapshots:
    """Bring the engine to its first state and drive it through one
    iteration with the window's own call, ``fit``, which also loads or
    compiles every program the window runs; keep what the reference
    needs."""
    if traffic["init"] == "planted":
        engine.restore(payload)
    else:
        engine.fit(0)                       # the engine's own random init
    t0 = time.perf_counter()
    p0 = engine.host_payload()
    spent = time.perf_counter() - t0
    hist = engine.fit(1)
    t0 = time.perf_counter()
    snap = Snapshots(topics=[p0["topics_global"],
                             engine.host_payload()["topics_global"]],
                     key0=np.asarray(p0["key"], np.uint32),
                     iteration=int(hist["iteration"][-1]),
                     D=np.asarray(engine.state.D),
                     W=np.asarray(engine.state.W))
    snap.seconds = spent + time.perf_counter() - t0
    return snap


@dataclasses.dataclass
class Window:
    """The measured window: one ``fit`` call of ``n_iters`` iterations."""
    n_iters: int
    wall: float
    compiles: int
    history: dict               # what the window's ``fit`` returned
    topics: np.ndarray = None   # the program's state after it (host)
    D: np.ndarray = None
    W: np.ndarray = None


def measure(engine, clock: CompileClock, n_iters: int,
            trace_dir: pathlib.Path | None = None) -> Window:
    import jax
    if trace_dir is not None:
        shutil.rmtree(trace_dir, ignore_errors=True)
        jax.profiler.start_trace(str(trace_dir))
    lowerings0 = clock.lowerings
    t0 = time.perf_counter()
    with jax.profiler.TraceAnnotation(WINDOW_SPAN):
        with jax.profiler.TraceAnnotation("bench.fit"):
            hist = engine.fit(n_iters)
    wall = time.perf_counter() - t0
    if trace_dir is not None:
        jax.profiler.stop_trace()
    return Window(n_iters, wall, clock.lowerings - lowerings0, hist)


def keep_final(engine, win: Window) -> None:
    win.topics = engine.host_payload()["topics_global"]
    win.D = np.asarray(engine.state.D)
    win.W = np.asarray(engine.state.W)


@dataclasses.dataclass
class Replay:
    """The reference's draws at the check's sample, for set-up's checked
    iteration and for the last iteration of the window's call, and its
    LLPT (and topics) wherever the window's call reported an LLPT."""
    ref: object
    step: np.ndarray      # (s, 3) draws: see ``reference.draw``
    window: np.ndarray    # (s, 3)
    llpt: dict            # iteration -> the reference's LLPT
    chain: dict           # iteration -> the reference's topics (device)


def replay(g, cfg: dict, snap: Snapshots, win: Window,
           sample: np.ndarray) -> Replay:
    """The plain reference over set-up's checked iteration (from z_0) and
    over every iteration of the window's own call, from the program's z_1
    on the reference's own draws: a sound program reproduces each draw, so
    nothing builds up from one iteration to the next. Every iteration but
    the last is drawn for every token; the last at the sample alone, unless
    the program reported its LLPT."""
    from bench import reference
    ref = reference.Reference(g.word_ids, g.doc_ids, n_docs=g.n_docs,
                              n_words=g.n_words, n_topics=g.n_topics,
                              alpha=cfg["alpha"], beta=cfg["beta"])
    rows = np.asarray(sample, np.int32)
    step, key = ref.next_topics(snap.topics[0], snap.key0, rows)
    reported = set(win.history["iteration"])
    z, llpt, chain = snap.topics[1], {}, {}
    last = snap.iteration + win.n_iters
    for it in range(snap.iteration + 1, last + 1):
        full = it < last or it in reported
        draws, key = ref.next_topics(z, key, None if full else rows)
        if full:
            z = draws[:, 0]
            if it in reported:
                llpt[it], chain[it] = ref.llpt(z), z
    window = draws[rows] if full else draws
    return Replay(ref, np.asarray(step), np.asarray(window), llpt, chain)


def compare(rep: Replay, snap: Snapshots, win: Window,
            sample: np.ndarray) -> dict:
    """The numbers the check holds to the cell's limits."""
    from bench import reference
    reported = dict(zip(win.history["iteration"], win.history["llpt"]))
    return {
        "topic_mismatch": reference.mismatch(snap.topics[1][sample],
                                             rep.step),
        "window_mismatch": reference.mismatch(win.topics[sample],
                                              rep.window),
        "count_gap": max(rep.ref.count_gap(snap.topics[1], snap.D, snap.W),
                         rep.ref.count_gap(win.topics, win.D, win.W)),
        # no LLPT to compare is no sound reading
        "llpt_gap": max((abs(float(reported[it]) - v) / abs(v)
                         for it, v in rep.llpt.items()),
                        default=float("inf")),
    }


def run(name: str, seed: int, seconds: float, trace: bool, *,
        t_start: float, require_tpu: bool = True,
        root: pathlib.Path = ROOT, bench: pathlib.Path = BENCH) -> dict:
    import jax
    from repro.lda.api import LDAEngine

    cell = load_cell(name, root, bench)
    device = devices(cell.chips, require_tpu)
    clock = CompileClock()
    g, corpus = make_corpus(cell, seed)
    payload = warm_payload(g, cell, seed) \
        if cell.traffic["init"] == "planted" else None
    engine = LDAEngine(corpus, lda_config(cell))
    del corpus
    log(f"[engine] backend={engine.backend_name} config={engine.config}")
    snap = checked_step(engine, cell.traffic, payload)
    setup_s = time.perf_counter() - t_start - snap.seconds
    log(f"[setup] setup_s={setup_s:.3f} compile_s={clock.seconds:.3f} "
        f"lowerings={clock.lowerings} check_copies_s={snap.seconds:.3f}")

    # -- the measured window ------------------------------------------------
    trace_dir = root / ".bench_trace"
    win = measure(engine, clock, window_iters(cell, seconds),
                  trace_dir if trace else None)
    tokens_per_s = g.n_tokens * win.n_iters / win.wall / cell.chips
    mem = jax.devices()[0].memory_stats() or {}
    device["memory_peak_bytes"] = int(mem.get("peak_bytes_in_use", 0))
    log(f"[window] iterations={win.n_iters} calls=1 wall_s={win.wall:.4f} "
        f"compiles_in_window={win.compiles} tokens_per_s={tokens_per_s:.1f}")
    keep_final(engine, win)
    del engine
    gc.collect()

    metrics = {}
    breakdown = None
    if trace:
        from bench import trace_reduce
        path = next(trace_dir.glob("plugins/profile/*/*.xplane.pb"))
        red = trace_reduce.reduce(trace_reduce.read(str(path)),
                                  WINDOW_SPAN, cell.chips)
        device["busy_s"] = red["busy_s"]
        device["window_s"] = red["window_s"]
        breakdown = red["breakdown"]
        shutil.rmtree(trace_dir, ignore_errors=True)
        ctx = {"tokens_per_s": tokens_per_s, "trace": red,
               "stats": win.history["stats"],
               "n_topics": int(cell.config["n_topics"]),
               "peak": roofline.peaks(device["kind"]) if require_tpu
               else None}
        for m in cell.per_layer:
            value = cell.readers[m["name"]](ctx)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    else:
        values = {"train_tokens_per_s": tokens_per_s, "setup_s": setup_s}
        for m in cell.end_to_end:
            metrics[m["name"]] = {"value": values[m["name"]],
                                  "unit": m["unit"]}

    # -- the check ------------------------------------------------------------
    t1 = time.perf_counter()
    sample = check_sample(g.n_tokens, seed)
    readings = compare(replay(g, cell.config, snap, win, sample), snap, win,
                       sample)
    readings["compiles_in_window"] = win.compiles
    limits = cell.cell["limits"]
    checks = {k: {"value": readings[k], "limit": limits[k]} for k in limits}
    correct = all(c["value"] <= c["limit"] for c in checks.values())
    log(f"[check] reference_s={time.perf_counter() - t1:.3f} "
        f"llpt_reported={win.history['llpt']}")
    result = {"correct": correct, "attempted": win.n_iters, "failed": 0,
              "metrics": metrics, "device": device}
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["checks"] = checks
    for k, c in checks.items():
        log(f"[check] {k} value={c['value']!r} limit={c['limit']!r}")
    return result
