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
    if len(devs) < chips:
        raise NoChip(f"the cell needs {chips} chips, JAX finds {len(devs)}")
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": chips}


def make_engine(corpus, cell: Cell):
    """The engine on exactly the cell's chips. Where JAX sees that many
    devices it is built as a user would build it, with the engine's own
    choice of backend; where it sees more, on the first ``chips`` of them:
    the single backend for one, a (``chips``, 1) data mesh for more."""
    import jax
    from repro.lda.api import LDAEngine
    cfg = lda_config(cell)
    if jax.device_count() == cell.chips:
        return LDAEngine(corpus, cfg)
    if cell.chips == 1:
        return LDAEngine(corpus, cfg, backend="single")
    from repro.runtime.compat import make_mesh
    mesh = make_mesh((cell.chips, 1), ("data", "model"),
                     devices=jax.devices()[:cell.chips])
    return LDAEngine(corpus, cfg, backend="distributed", mesh=mesh)


def check_held_devices(engine, chips: int) -> None:
    """Log the devices the engine's state lives on; a state outside the
    cell's first ``chips`` devices would measure another cell."""
    import jax
    held = sorted({d.id for leaf in jax.tree.leaves(engine.state)
                   if isinstance(leaf, jax.Array) for d in leaf.devices()})
    ours = [d.id for d in jax.devices()[:chips]]
    log(f"[engine] holds devices {held} of the cell's {ours}")
    if not set(held) <= set(ours):
        raise RuntimeError(f"the engine holds devices {held}, outside the "
                           f"cell's {ours}")


def memory_peaks(chips: int) -> list[int]:
    """``peak_bytes_in_use`` of each of the cell's devices (0 where the
    backend reports none)."""
    import jax
    return [int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
            for d in jax.devices()[:chips]]


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
class Counts:
    """The program's counts in document order, whatever its layout."""
    D: np.ndarray         # (n_docs, K)
    W: np.ndarray         # (V, K)
    stray: int = 0        # counts held in rows that are no document's own


def document_counts(state, trainer) -> Counts:
    """D as (n_docs, K) and W as (V, K) from the engine's state.

    The single backend's dense state is taken as it is. A distributed dense
    state keeps D as (shards, rows, K): shard ``s``'s first
    ``docs_per_shard[s]`` rows are documents ``doc_map[s]``, the rest pad
    rows. A count in a pad row, or in a second row of one document, is
    counted as ``stray`` and shows in ``count_gap``. Any other layout is
    refused by name rather than read as something it is not."""
    from repro.lda.distributed import DistLDAState
    from repro.lda.model import LDAState
    if isinstance(state, LDAState):
        return Counts(np.asarray(state.D), np.asarray(state.W))
    if isinstance(state, DistLDAState) and trainer.sc.owns is None:
        return sharded_counts(np.asarray(state.D), np.asarray(state.W),
                              trainer.sc.doc_map, trainer.sc.docs_per_shard,
                              trainer.corpus.n_docs)
    layout = type(state).__name__
    if isinstance(state, DistLDAState):
        layout += " with documents replicated across shards"
    raise TypeError(f"no document-order reading of the counts of a {layout}")


def sharded_counts(D_sh: np.ndarray, W: np.ndarray, doc_map: np.ndarray,
                   docs_per_shard: np.ndarray, n_docs: int) -> Counts:
    """Document-order counts from per-shard D rows (see
    ``document_counts``); W is replicated and taken as it is."""
    if D_sh.ndim != 3 or W.ndim != 2 or W.shape[1] != D_sh.shape[2]:
        raise ValueError(f"sharded D {D_sh.shape} and replicated W "
                         f"{W.shape}: expected (S, rows, K) and (V, K)")
    D = np.zeros((n_docs, D_sh.shape[2]), D_sh.dtype)
    held = np.zeros(n_docs, bool)
    stray = 0
    for s, rows in enumerate(D_sh):
        nd = int(docs_per_shard[s])
        ids = np.asarray(doc_map[s][:nd], np.int64)
        stray += int(np.abs(rows[nd:]).sum(dtype=np.int64))
        own = np.zeros(nd, bool)
        own[np.unique(ids, return_index=True)[1]] = True
        own &= ~held[ids]
        stray += int(np.abs(rows[:nd][~own]).sum(dtype=np.int64))
        D[ids[own]] = rows[:nd][own]
        held[ids[own]] = True
    return Counts(D, W, stray)


@dataclasses.dataclass
class Snapshots:
    """What set-up's checked iteration leaves for the check."""
    topics: list          # z_0, z_1 (host, canonical order)
    key0: np.ndarray      # the key that drew z_1
    iteration: int        # the engine's iteration after it
    counts: Counts        # the program's counts after it
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
                     counts=document_counts(engine.state, engine.trainer))
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
    counts: Counts = None


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
    win.counts = document_counts(engine.state, engine.trainer)


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


def count_gap(ref, topics, counts: Counts) -> int:
    """Σ|ΔD| + Σ|ΔW| against the recount, plus the counts the program
    holds where no document is."""
    return ref.count_gap(topics, counts.D, counts.W) + counts.stray


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
        "count_gap": max(count_gap(rep.ref, snap.topics[1], snap.counts),
                         count_gap(rep.ref, win.topics, win.counts)),
        # no LLPT to compare is no sound reading
        "llpt_gap": max((abs(float(reported[it]) - v) / abs(v)
                         for it, v in rep.llpt.items()),
                        default=float("inf")),
    }


def run(name: str, seed: int, seconds: float, trace: bool, *,
        t_start: float, require_tpu: bool = True,
        root: pathlib.Path = ROOT, bench: pathlib.Path = BENCH) -> dict:
    cell = load_cell(name, root, bench)
    device = devices(cell.chips, require_tpu)
    clock = CompileClock()
    g, corpus = make_corpus(cell, seed)
    payload = warm_payload(g, cell, seed) \
        if cell.traffic["init"] == "planted" else None
    engine = make_engine(corpus, cell)
    del corpus
    log(f"[engine] backend={engine.backend_name} config={engine.config}")
    snap = checked_step(engine, cell.traffic, payload)
    check_held_devices(engine, cell.chips)
    setup_s = time.perf_counter() - t_start - snap.seconds
    log(f"[setup] setup_s={setup_s:.3f} compile_s={clock.seconds:.3f} "
        f"lowerings={clock.lowerings} check_copies_s={snap.seconds:.3f}")

    # -- the measured window ------------------------------------------------
    trace_dir = root / ".bench_trace"
    win = measure(engine, clock, window_iters(cell, seconds),
                  trace_dir if trace else None)
    tokens_per_s = g.n_tokens * win.n_iters / win.wall / cell.chips
    peaks = memory_peaks(cell.chips)
    device["memory_peak_bytes"] = max(peaks)
    device["memory_peak_bytes_per_chip"] = peaks
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
        f"memory_peak_bytes_after={memory_peaks(1)[0]} "
        f"llpt_reported={win.history['llpt']}")
    result = {"correct": correct, "attempted": win.n_iters, "failed": 0,
              "metrics": metrics, "device": device}
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["checks"] = checks
    for k, c in checks.items():
        log(f"[check] {k} value={c['value']!r} limit={c['limit']!r}")
    return result
