"""The per-layer readers of the training iteration's phases: each reads its
program's device time, or its counter, from a hand-made ``ctx``, and gives
None where the run has none of it."""

import pytest

from bench import harness

SHARES = {
    "count_update_share.train": ("jit_update_counts",),
}
MODULE_S = {"jit_true_divide": 0.1, "jit__sample_reference": 8.2,
            "jit_update_counts": 0.5, "jit_token_ll": 0.9}


def _reader(name):
    return harness._load_reader(harness.BENCH / "metrics" / f"{name}.py")


def _ctx(module_s, busy_s=10.0, stats=()):
    return {"trace": {"busy_s": busy_s, "window_s": busy_s,
                      "module_s": module_s},
            "stats": list(stats)}


@pytest.mark.parametrize("name", sorted(SHARES))
def test_share_reads_its_program(name):
    want = 100.0 * sum(MODULE_S[m] for m in SHARES[name]) / 10.0
    assert _reader(name)(_ctx(MODULE_S)) == pytest.approx(want)


@pytest.mark.parametrize("name", sorted(SHARES))
def test_share_none_without_its_program(name):
    # a trace of a fused program, where no phase is a program of its own
    fused = {"jit_multi": 16.0, "jit_token_ll": 1.0}
    assert _reader(name)(_ctx(fused)) is None
    assert _reader(name)({"stats": []}) is None           # not traced
    assert _reader(name)(_ctx({SHARES[name][0]: 1.0}, busy_s=0.0)) is None


def test_phase2_yield_reads_the_counter():
    read = _reader("phase2_yield.train")
    stats = [{"frac_skipped": 0.8, "frac_phase2_slots": 1.0},
             {"frac_skipped": 0.6, "frac_phase2_slots": 0.5}]
    assert read({"stats": stats}) == pytest.approx(100.0 * (0.2 + 0.8) / 2)


@pytest.mark.parametrize("stats", [
    [],
    [{"frac_skipped": 0.8}],                       # no counter (older program)
    [{"frac_skipped": 0.8, "frac_phase2_slots": float("nan")}],
    [{"frac_skipped": 1.0, "frac_phase2_slots": 0.0}],   # nothing drawn
])
def test_phase2_yield_none_without_the_counter(stats):
    assert _reader("phase2_yield.train")({"stats": stats}) is None
