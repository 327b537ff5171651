"""From a profiler trace to the numbers the benchmark reports.

``read`` turns an ``.xplane.pb`` into plain intervals: per device its
operations and its programs ("modules"), and the host's spans (the
benchmark's ``TraceAnnotation`` spans and JAX's own host events). The rest is
interval arithmetic on those lists, so it can be checked on intervals made by
hand. Times are in nanoseconds on the profiler's common clock.
"""

from __future__ import annotations

import bisect
import dataclasses
import re

DEVICE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
# HLO collective opcodes, async ``-start``/``-done`` forms included
COLLECTIVE = re.compile(r"(?:all-reduce|all-gather|reduce-scatter|"
                        r"collective-permute|all-to-all)(?:-start|-done)?")
# the opcode in an op's HLO text: ``%psum.48 = s32[..]{..} all-reduce(..``
OPCODE = re.compile(r" = .*?(?:^|\s)([a-z][a-z0-9-]*)\(")


@dataclasses.dataclass
class Trace:
    ops: dict        # device id -> [(name, start, end)]
    modules: dict    # device id -> [(name, start, end)]
    host: list       # [(name, start, end)]


def module_name(event_name: str) -> str:
    """``jit_token_ll(123)`` -> ``jit_token_ll``."""
    return re.sub(r"\(.*$", "", event_name).strip()


def read(path: str) -> Trace:
    import jax
    pd = jax.profiler.ProfileData.from_file(path)
    ops, modules, host = {}, {}, []
    for plane in pd.planes:
        if plane.name.startswith(DEVICE_PREFIX):
            dev = plane.name[len(DEVICE_PREFIX):]
            for line in plane.lines:
                if line.name == OPS_LINE:
                    dst = ops.setdefault(dev, [])
                elif line.name == MODULES_LINE:
                    dst = modules.setdefault(dev, [])
                else:
                    continue
                for e in line.events:
                    dst.append((e.name, e.start_ns, e.start_ns + e.duration_ns))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.duration_ns > 0:
                        host.append((e.name, e.start_ns,
                                     e.start_ns + e.duration_ns))
    return Trace(ops=ops, modules=modules, host=host)


def span(trace: Trace, name: str) -> tuple[float, float]:
    """The one host span of this name (the measured window)."""
    found = [(s, e) for n, s, e in trace.host if n == name]
    if len(found) != 1:
        raise ValueError(f"expected one host span {name!r}, found {len(found)}")
    return found[0]


def union(intervals, lo: float, hi: float) -> list[tuple[float, float]]:
    """Merged [start, end) intervals, clipped to [lo, hi)."""
    clipped = sorted((max(s, lo), min(e, hi)) for s, e in intervals
                     if e > lo and s < hi)
    out: list[list[float]] = []
    for s, e in clipped:
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def length(intervals) -> float:
    return sum(e - s for s, e in intervals)


def gaps(busy, lo: float, hi: float) -> list[tuple[float, float]]:
    """The idle stretches of [lo, hi) between merged busy intervals."""
    out, t = [], lo
    for s, e in busy:
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if hi > t:
        out.append((t, hi))
    return out


def busy_intervals(trace: Trace, dev: str, lo: float, hi: float):
    events = trace.ops.get(dev) or trace.modules.get(dev) or []
    return union(((s, e) for _, s, e in events), lo, hi)


def module_time(trace: Trace, dev: str, lo: float, hi: float) -> dict:
    """Device seconds inside each program, by module name: the busy time
    that falls within the program's own executions."""
    before = busy_before(busy_intervals(trace, dev, lo, hi))
    by_name: dict[str, list] = {}
    for name, s, e in trace.modules.get(dev, []):
        by_name.setdefault(module_name(name), []).append((s, e))
    return {name: sum(before(e) - before(s) for s, e in union(ivs, lo, hi))
            * 1e-9 for name, ivs in by_name.items()}


def is_collective(op_name: str) -> bool:
    """Whether a trace op is a collective, by its HLO opcode: a TPU trace
    names each op by its HLO text, whose instruction name is JAX's (the
    W-delta all-reduce is ``%psum.48 = ... all-reduce(...)``); a bare name
    (``all-reduce.3``) is its opcode and a number."""
    m = OPCODE.search(op_name)
    opcode = m.group(1) if m else re.sub(r"\.\d+$", "", op_name.lstrip("%"))
    return COLLECTIVE.fullmatch(opcode) is not None


def collective_time(trace: Trace, dev: str, lo: float, hi: float) -> float:
    """Device seconds inside collective operations: the busy time that
    falls within the union of their intervals, so a collective nested in
    another operation's interval (a ``while``'s) counts once."""
    before = busy_before(busy_intervals(trace, dev, lo, hi))
    ivs = [(s, e) for name, s, e in trace.ops.get(dev, [])
           if is_collective(name)]
    return sum(before(e) - before(s) for s, e in union(ivs, lo, hi)) * 1e-9


def busy_before(merged):
    """``t -> busy length before t`` for merged intervals."""
    starts = [s for s, _ in merged]
    prefix = [0.0]
    for s, e in merged:
        prefix.append(prefix[-1] + e - s)

    def before(t: float) -> float:
        i = bisect.bisect_right(starts, t) - 1
        if i < 0:
            return 0.0
        s, e = merged[i]
        return prefix[i] + min(t, e) - s
    return before


def label_gaps(trace: Trace, idle, n: int = 10, exclude=()):
    """The ``n`` longest idle stretches, each named by the innermost host
    span that covers its middle ("idle" where none does)."""
    out = []
    for s, e in sorted(idle, key=lambda g: g[0] - g[1])[:n]:
        mid = (s + e) / 2
        cover = [(hs, he, name) for name, hs, he in trace.host
                 if hs <= mid < he and name not in exclude]
        label = min(cover, key=lambda c: c[1] - c[0])[2] if cover else "idle"
        out.append([label, (e - s) * 1e-9])
    return out


def reduce(trace: Trace, window_span: str = "bench.window",
           n_chips: int = 1) -> dict:
    """busy and window seconds, per-module and collective device seconds
    and the breakdown, over the first ``n_chips`` devices of the trace. The
    breakdown's device operations are the programs (XLA modules) that
    took most device time: they partition it, where the trace's
    operations nest (a ``while`` holds its body's operations)."""
    lo, hi = span(trace, window_span)
    devs = sorted(trace.ops.keys() | trace.modules.keys(),
                  key=lambda d: (len(d), d))[:n_chips]
    if not devs:
        raise ValueError("the trace holds no device operations")
    busy = {d: busy_intervals(trace, d, lo, hi) for d in devs}
    busy_s = sum(length(b) for b in busy.values()) * 1e-9 / len(devs)
    modules: dict[str, float] = {}
    for d in devs:
        for name, s in module_time(trace, d, lo, hi).items():
            modules[name] = modules.get(name, 0.0) + s / len(devs)
    d0 = devs[0]
    top = sorted(modules.items(), key=lambda kv: -kv[1])[:10]
    return {
        "busy_s": busy_s,
        "window_s": (hi - lo) * 1e-9,
        "module_s": modules,
        "collective_s": sum(collective_time(trace, d, lo, hi)
                            for d in devs) / len(devs),
        "breakdown": {
            "device_ops": [[name, s] for name, s in top],
            "idle_gaps": label_gaps(trace, gaps(busy[d0], lo, hi),
                                    exclude=(window_span,)),
        },
    }
