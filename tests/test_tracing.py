"""The training iteration's phases carry names where they run.

  * ``fit``'s ``history["lowered"]`` (the program's compile counter) names
    the programs a call lowered: on the default path (stepwise,
    three-branch, survivor capacity derived) the sampler and the count
    rebuild, whichever phase-2 branch the sampler takes.
  * ``frac_phase2_slots`` counts the exact-draw slots phase 2 computed,
    ``phase2_compacted`` the branch that computed them.
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
PHASES = ["jit_update_counts", "jit__sample_adaptive"]


@pytest.fixture(scope="module")
def corpus():
    return synthetic_lda_corpus(0, n_docs=60, n_words=80, n_topics=8,
                                mean_doc_len=40)


def _engine(corpus, n_topics, **kw):
    return LDAEngine(corpus, LDAConfig(n_topics=n_topics, tile_size=512,
                                       eval_every=5, **kw))


def _converged_payload(eng):
    """The engine's payload with every token of a word on one topic: the
    skip test passes nearly everywhere, so the sampler compacts."""
    payload = eng.host_payload()
    words = np.asarray(eng.trainer.corpus.word_ids)
    return dict(payload, topics_global=(words % eng.config.n_topics)
                .astype(payload["topics_global"].dtype))


def test_fit_lowers_the_named_phase_programs(corpus):
    eng = _engine(corpus, 13)
    log = []
    first = eng.fit(1, log_fn=log.append)
    lowered = first["lowered"]
    assert [n for n in lowered if n in PHASES] == PHASES
    assert "jit_token_ll" in lowered                 # the eval, (·, K) shapes
    assert not any("lowered after" in line for line in log)
    # a second call runs the compiled programs: it lowers nothing, though
    # the sampler switches to its other phase-2 branch as tokens converge
    second = eng.fit(29, log_fn=log.append)
    assert second["lowered"] == []
    assert [s["phase2_compacted"] for s in first["stats"]] == [0.0]
    branches = [s["phase2_compacted"] for s in second["stats"]]
    assert branches[0] == 0.0 and branches[-1] == 1.0
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
    # the default path draws every token of a fresh engine's first
    # iteration: almost nothing skips yet
    hist = _engine(corpus, 14).fit(2)
    assert [s["frac_phase2_slots"] for s in hist["stats"]] == [1.0]
    assert [s["phase2_compacted"] for s in hist["stats"]] == [0.0]


def test_phase2_slots_default_path_compacts(corpus):
    eng = _engine(corpus, 16)
    eng.fit(0)
    hist = eng.restore(_converged_payload(eng)).fit(1)
    n, capacity = eng.trainer.n_padded_tokens, eng.trainer.plan.capacity
    (st,) = hist["stats"]
    assert st["phase2_compacted"] == 1.0
    assert st["frac_phase2_slots"] == pytest.approx(
        _expected_slots(st, n, capacity), abs=1e-6)
    assert st["frac_phase2_slots"] < 1.0


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
