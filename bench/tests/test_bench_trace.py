"""The reduction from a profiler trace to busy, idle and per-module time."""

import time

import pytest

from bench import trace_reduce as tr


def _trace():
    # device 0: ops [0,10) [5,20) [30,40) [90,120); modules A=[0,25) B=[28,45)
    # device 1: ops [0,50)
    ops = {"0": [("add", 0, 10), ("mul", 5, 20), ("add", 30, 40),
                 ("late", 90, 120)],
           "1": [("add", 0, 50)]}
    modules = {"0": [("jit_a(1)", 0, 25), ("jit_b(7)", 28, 45),
                     ("jit_a(1)", 88, 125)],
               "1": [("jit_a(1)", 0, 50)]}
    host = [("bench.window", 0, 100), ("fit", 0, 100), ("eval", 20, 30)]
    return tr.Trace(ops=ops, modules=modules, host=host)


def test_union_and_gaps():
    u = tr.union([(0, 10), (5, 20), (30, 40), (90, 120)], 0, 100)
    assert u == [(0, 20), (30, 40), (90, 100)]
    assert tr.length(u) == 40
    assert tr.gaps(u, 0, 100) == [(20, 30), (40, 90)]


def test_reduce_one_chip():
    red = tr.reduce(_trace(), "bench.window", n_chips=1)
    assert red["window_s"] == pytest.approx(100e-9)
    assert red["busy_s"] == pytest.approx(40e-9)
    # module a: busy in [0,25) -> 20, in [88,100) -> 10; module b: [30,40)
    assert red["module_s"]["jit_a"] == pytest.approx(30e-9)
    assert red["module_s"]["jit_b"] == pytest.approx(10e-9)
    assert red["breakdown"]["device_ops"] == [
        ["jit_a", pytest.approx(30e-9)], ["jit_b", pytest.approx(10e-9)]]
    gaps = red["breakdown"]["idle_gaps"]
    assert gaps[0] == ["fit", pytest.approx(50e-9)]     # [40, 90)
    assert gaps[1] == ["eval", pytest.approx(10e-9)]    # [20, 30)


def test_reduce_averages_chips():
    red = tr.reduce(_trace(), "bench.window", n_chips=2)
    assert red["busy_s"] == pytest.approx((40e-9 + 50e-9) / 2)


def test_no_device_is_an_error():
    t = tr.Trace(ops={}, modules={}, host=[("bench.window", 0, 10)])
    with pytest.raises(ValueError):
        tr.reduce(t)


def test_recorded_trace(tmp_path):
    """A trace recorded here: its spans come back with their lengths, and
    the reduction over them gives the recorded busy and idle time."""
    jax = pytest.importorskip("jax")
    with jax.profiler.trace(str(tmp_path)):
        with jax.profiler.TraceAnnotation("bench.window"):
            for i in range(3):
                with jax.profiler.TraceAnnotation(f"op.{i}"):
                    time.sleep(0.05)
                with jax.profiler.TraceAnnotation("host.wait"):
                    time.sleep(0.03)
    path = next(tmp_path.glob("plugins/profile/*/*.xplane.pb"))
    rec = tr.read(str(path))
    lo, hi = tr.span(rec, "bench.window")
    ops = [(n, s, e) for n, s, e in rec.host if n.startswith("op.")]
    assert len(ops) == 3
    for _, s, e in ops:
        assert 0.05e9 <= e - s < 0.09e9
    # the recorded spans standing in for one device's operations
    dev = tr.Trace(ops={"0": ops}, modules={"0": [("jit_op(1)", lo, hi)]},
                   host=rec.host)
    red = tr.reduce(dev, "bench.window")
    assert red["window_s"] == pytest.approx((hi - lo) * 1e-9)
    assert red["busy_s"] == pytest.approx(
        sum(e - s for _, s, e in ops) * 1e-9)
    assert red["module_s"]["jit_op"] == pytest.approx(red["busy_s"])
    # the gaps between the ops are the host's waits: each is named by the
    # innermost host event around it (the sleep inside "host.wait")
    long_gaps = [g for g in red["breakdown"]["idle_gaps"] if g[1] > 0.02]
    assert len(long_gaps) >= 2
    assert {g[0] for g in long_gaps} <= {"host.wait", "$time sleep"}
