"""``collective_share.train`` reads the collective time of a traced run
over its busy time, and gives None where there is nothing to read."""

import pytest

from bench import harness, trace_reduce


def _read(ctx):
    return harness._load_reader(
        harness.BENCH / "metrics" / "collective_share.train.py")(ctx)


def _trace(ops):
    """A two-chip trace of the window [0, 100 ms) with these ops per chip."""
    return trace_reduce.Trace(
        ops={str(d): list(o) for d, o in enumerate(ops)}, modules={},
        host=[("bench.window", 0, 100_000_000)])


def test_reads_collective_time_over_busy_time():
    # chip 0: 40 ms of sampling and a 10 ms all-reduce; chip 1: 50 ms of
    # sampling and a 2 ms all-reduce. Means: busy 51 ms, collectives 6 ms.
    red = trace_reduce.reduce(_trace([
        [("fusion.1", 0, 40_000_000),
         ("%psum.48 = s32[141043,1000]{0,1} all-reduce(%x)",
          40_000_000, 50_000_000)],
        [("fusion.1", 0, 50_000_000),
         ("all-reduce.13", 50_000_000, 52_000_000)],
    ]), n_chips=2)
    assert _read({"trace": red}) == pytest.approx(100.0 * 6.0 / 51.0)


def test_zero_without_collectives():
    red = trace_reduce.reduce(_trace([[("fusion.1", 0, 40_000_000)],
                                      [("fusion.2", 0, 30_000_000)]]),
                              n_chips=2)
    assert red["collective_s"] == 0.0
    assert _read({"trace": red}) == 0.0


@pytest.mark.parametrize("ctx", [
    {},                                                     # not traced
    {"trace": None},
    {"trace": {"busy_s": 1.0, "window_s": 1.0, "module_s": {}}},  # no reading
    {"trace": {"busy_s": 0.0, "window_s": 1.0, "collective_s": 0.0}},
])
def test_none_without_a_reading(ctx):
    assert _read(ctx) is None
