"""The training iteration's phases carry names where they run.

  * ``fit``'s ``history["lowered"]`` (the program's compile counter) names
    the programs a call lowered: on the default path (stepwise,
    three-branch, no survivor capacity) the sampler and the count rebuild.
  * ``frac_phase2_slots`` counts the exact-draw slots phase 2 computed.
  * The stepwise loop's spans land in a profiler trace.

Each test builds its engine at a topic count no other test uses, so the
programs it watches lower inside it whatever ran before in the process.
"""

import math
import pathlib

import jax
import numpy as np
import pytest

from repro.lda.api import LDAEngine
from repro.lda.corpus import synthetic_lda_corpus
from repro.lda.model import LDAConfig
from repro.runtime import compiles

# the default step's programs, in the order a fresh engine lowers them
# (the init's count build lowers ``jit_update_counts`` first)
PHASES = ["jit_update_counts", "jit__sample_reference"]


@pytest.fixture(scope="module")
def corpus():
    return synthetic_lda_corpus(0, n_docs=60, n_words=80, n_topics=8,
                                mean_doc_len=40)


def _engine(corpus, n_topics, **kw):
    return LDAEngine(corpus, LDAConfig(n_topics=n_topics, tile_size=512,
                                       eval_every=5, **kw))


def test_fit_lowers_the_named_phase_programs(corpus):
    eng = _engine(corpus, 13)
    log = []
    lowered = eng.fit(1, log_fn=log.append)["lowered"]
    assert [n for n in lowered if n in PHASES] == PHASES
    assert "jit_token_ll" in lowered                 # the eval, (·, K) shapes
    assert not any("lowered after" in line for line in log)
    # a second call runs the compiled programs: it lowers nothing
    assert eng.fit(2, log_fn=log.append)["lowered"] == []
    assert not any("lowered after" in line for line in log)
    assert eng.history["lowered"] == lowered


def test_late_lowering_is_named():
    log = []
    late = compiles.LateLowerings(log.append)
    late.settle(1)                        # the first iteration: mark only
    late.settle(2)
    assert log == []

    @jax.jit
    def lowered_late(x):
        return x * 3 + 1

    lowered_late(np.arange(7, dtype=np.float32))
    late.settle(3)
    assert len(log) == 1 and "jit_lowered_late" in log[0]
    assert log[0].startswith("iter=   3")


def _expected_slots(stats, n, capacity):
    n_surv = n - round(stats["frac_skipped"] * n)
    return min(math.ceil(n_surv / capacity) * capacity, n) / n


def test_phase2_slots_reference_path(corpus):
    hist = _engine(corpus, 14).fit(2)
    assert [s["frac_phase2_slots"] for s in hist["stats"]] == [1.0]


@pytest.mark.parametrize("capacity", [64, 777, 100_000])
@pytest.mark.parametrize("fused", [False, True], ids=["compacted", "fused"])
def test_phase2_slots_compacted_paths(corpus, capacity, fused):
    eng = _engine(corpus, 15, survivor_capacity=capacity, fused=fused)
    hist = eng.fit(6)
    n = eng.trainer.n_padded_tokens
    assert len(hist["stats"]) == 2             # iterations 1 and 5
    for st in hist["stats"]:
        assert 0.0 < st["frac_phase2_slots"] <= 1.0
        assert st["frac_phase2_slots"] == pytest.approx(
            _expected_slots(st, n, capacity), abs=1e-6)
    if capacity < n:
        # later iterations skip tokens, so whole chunks stop running
        assert hist["stats"][-1]["frac_phase2_slots"] < 1.0


def test_fit_spans_in_a_profiler_trace(corpus, tmp_path):
    eng = _engine(corpus, 17)
    eng.fit(1)
    with jax.profiler.trace(str(tmp_path)):
        eng.fit(2)
    path = next(pathlib.Path(tmp_path).glob("plugins/profile/*/*.xplane.pb"))
    data = jax.profiler.ProfileData.from_file(str(path))
    spans = {}
    for plane in data.planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith("lda."):
                        spans.setdefault(ev.name, []).append(ev)
    assert len(spans["lda.iteration"]) == 2
    assert len(spans["lda.eval"]) == 1
    assert len(spans["lda.stats"]) == 1
    steps = sorted(dict(ev.stats)["step_num"]
                   for ev in spans["lda.iteration"])
    assert steps == [1, 2]
