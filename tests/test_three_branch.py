"""Tests for the paper's core contribution: three-branch sampling (Eq 6-10).

The load-bearing properties:
  1. Eq 9/10: S' <= S_est for any counts (hypothesis property test).
  2. The skip theorem: a skipped token's exact sample is K1 (never changes
     the distribution).
  3. Three-branch sampling induces exactly p ∝ (D[d]+α)∘Ŵ[v] (stratified-u
     total-variation check) — same distribution as two-branch.
  4. The default sampler, on either branch, and a pinned survivor
     capacity are bit-identical to the reference path.
  5. End-to-end: LLPT rises; skip fraction grows over iterations (Fig 12b)
     and with g (paper parameter study).
"""

import dataclasses
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _hyp import given, settings, st

from repro.core import esca, three_branch
from repro.lda.model import LDAConfig
from repro.lda.trainer import LDATrainer

jax.config.update("jax_platform_name", "cpu")


def _random_state(seed, n_docs=30, n_words=40, K=12, n=800):
    rng = np.random.default_rng(seed)
    word_ids = np.sort(rng.integers(0, n_words, n)).astype(np.int32)
    doc_ids = rng.integers(0, n_docs, n).astype(np.int32)
    topics = rng.integers(0, K, n).astype(np.int32)
    D = np.zeros((n_docs, K), np.int32)
    W = np.zeros((n_words, K), np.int32)
    np.add.at(D, (doc_ids, topics), 1)
    np.add.at(W, (word_ids, topics), 1)
    return (jnp.asarray(word_ids), jnp.asarray(doc_ids), jnp.asarray(topics),
            jnp.asarray(D), jnp.asarray(W))


@settings(max_examples=20, deadline=None)
@given(seed=st.integers(0, 10_000), g=st.integers(1, 4))
def test_s_est_upper_bounds_s_prime(seed, g):
    """Eq 9/10: the g-term tail estimate dominates the true S'."""
    word_ids, doc_ids, _, D, W = _random_state(seed)
    alpha, beta = 50.0 / 12, 0.01
    W_hat = esca.compute_w_hat(W, beta)
    sw = three_branch.word_stats(W_hat, g=g, alpha=alpha)
    u = jnp.zeros(word_ids.shape[0], jnp.float32)
    dec = three_branch.skip_phase(u, word_ids, doc_ids, D, sw, g=g, alpha=alpha)
    # true S' = sum_k D[d][k]*W_hat[v][k] − a1*b1
    Wv = np.asarray(W_hat)[np.asarray(word_ids)]
    Dd = np.asarray(D, np.float32)[np.asarray(doc_ids)]
    k1 = np.asarray(sw.k[:, 0])[np.asarray(word_ids)]
    a1 = np.asarray(sw.a[:, 0])[np.asarray(word_ids)]
    b1 = Dd[np.arange(len(k1)), k1]
    s_true = (Wv * Dd).sum(-1) - a1 * b1
    assert np.all(np.asarray(dec.s_est) >= s_true - 1e-4), \
        (np.asarray(dec.s_est) - s_true).min()


@settings(max_examples=15, deadline=None)
@given(seed=st.integers(0, 10_000))
def test_skip_theorem(seed):
    """Skipped tokens would have sampled K1 under the exact sampler."""
    word_ids, doc_ids, _, D, W = _random_state(seed)
    alpha, beta = 50.0 / 12, 0.01
    W_hat = esca.compute_w_hat(W, beta)
    sw = three_branch.word_stats(W_hat, g=2, alpha=alpha)
    u = jax.random.uniform(jax.random.PRNGKey(seed), word_ids.shape,
                           dtype=jnp.float32)
    dec = three_branch.skip_phase(u, word_ids, doc_ids, D, sw, g=2, alpha=alpha)
    topics_exact, _ = three_branch.exact_three_branch(
        u, word_ids, doc_ids, sw.k[:, 0], D, W_hat, alpha=alpha, tile_size=256)
    viol = np.asarray(dec.skip & (topics_exact != dec.k1))
    assert viol.sum() == 0


def test_three_branch_distribution_matches_exact_p():
    """Stratified-u sweep: induced topic histogram == p ∝ (D+α)∘Ŵ."""
    word_ids, doc_ids, _, D, W = _random_state(7)
    K = 12
    alpha, beta = 50.0 / K, 0.01
    W_hat = esca.compute_w_hat(W, beta)
    sw = three_branch.word_stats(W_hat, g=2, alpha=alpha)
    for tok in (0, 100, 500):
        v, d = int(word_ids[tok]), int(doc_ids[tok])
        p = (np.asarray(D[d]) + alpha) * np.asarray(W_hat[v])
        p = p / p.sum()
        n = 100_000
        us = jnp.asarray((np.arange(n) + 0.5) / n, jnp.float32)
        t3, _ = three_branch.exact_three_branch(
            us, jnp.full(n, v, jnp.int32), jnp.full(n, d, jnp.int32),
            sw.k[:, 0], D, W_hat, alpha=alpha, tile_size=8192)
        h = np.bincount(np.asarray(t3), minlength=K) / n
        assert 0.5 * np.abs(h - p).sum() < 1e-3


def test_three_branch_matches_two_branch_distribution():
    """Both samplers induce the same distribution (different u→topic maps)."""
    word_ids, doc_ids, topics, D, W = _random_state(11)
    K = 12
    alpha, beta = 50.0 / K, 0.01
    W_hat = esca.compute_w_hat(W, beta)
    v, d = int(word_ids[50]), int(doc_ids[50])
    n = 100_000
    us = jnp.asarray((np.arange(n) + 0.5) / n, jnp.float32)
    vv = jnp.full(n, v, jnp.int32)
    dd = jnp.full(n, d, jnp.int32)
    t2, _ = esca.sample_two_branch(jax.random.PRNGKey(0), vv, dd,
                                   jnp.zeros(n, jnp.int32), D, W_hat,
                                   alpha=alpha, tile_size=8192)
    # two-branch uses its own key; rebuild with stratified u via internals
    from repro.core.esca import _sample_token
    t2 = jax.vmap(lambda u: _sample_token(u, D[d], W_hat[v],
                                          jnp.float32(alpha))[0])(us)
    sw = three_branch.word_stats(W_hat, g=2, alpha=alpha)
    t3, _ = three_branch.exact_three_branch(us, vv, dd, sw.k[:, 0], D, W_hat,
                                            alpha=alpha, tile_size=8192)
    h2 = np.bincount(np.asarray(t2), minlength=K) / n
    h3 = np.bincount(np.asarray(t3), minlength=K) / n
    assert 0.5 * np.abs(h2 - h3).sum() < 1e-3


@pytest.fixture(scope="module")
def states(small_corpus):
    """A fresh state and a trained one where most tokens skip, on a
    trainer whose padded N (2,368) is not a multiple of the derived
    capacity (48)."""
    cfg = LDAConfig(n_topics=16, tile_size=16, eval_every=5)
    tr = LDATrainer(small_corpus, cfg, _from_engine=True)
    fresh = tr.init_state()
    trained = fresh
    for _ in range(30):
        trained, _ = tr.step(trained)
    return tr, {"fresh": fresh, "trained": trained}


# the derived plan forced to each branch, as it picks, and pinned
PLANS = {
    "derived": lambda p: p,
    "derived-dense": lambda p: dataclasses.replace(p, compact_below=0.0),
    "derived-compacted": lambda p: dataclasses.replace(
        p, compact_below=math.inf),
    "pinned-64": lambda p: three_branch.Plan(g=p.g, tile_size=p.tile_size,
                                             capacity=64),
    "pinned-777": lambda p: three_branch.Plan(g=p.g, tile_size=p.tile_size,
                                              capacity=777),
    "pinned-100000": lambda p: three_branch.Plan(
        g=p.g, tile_size=p.tile_size, capacity=100_000),
}


@pytest.mark.parametrize("state_name", ["fresh", "trained"])
@pytest.mark.parametrize("plan_name", list(PLANS))
def test_compacted_path_equals_reference(states, plan_name, state_name):
    tr, by_name = states
    state, cfg = by_name[state_name], tr.config
    n = tr.n_padded_tokens
    assert n % tr.plan.capacity != 0
    plan = PLANS[plan_name](tr.plan)
    key = jax.random.PRNGKey(9)
    plan_ref = three_branch.Plan(g=plan.g, tile_size=plan.tile_size,
                                 capacity=None)
    t_ref, s_ref = three_branch.sample(
        key, plan_ref, tr.word_ids, tr.doc_ids, state.topics,
        state.D, state.W, cfg)
    t_cap, s_cap = three_branch.sample(
        key, plan, tr.word_ids, tr.doc_ids, state.topics,
        state.D, state.W, cfg)
    assert bool(jnp.all(t_ref == t_cap))
    for field in ("frac_skipped", "frac_m_final", "frac_unchanged",
                  "frac_at_max"):
        assert float(getattr(s_ref, field)) == float(getattr(s_cap, field))
    # the branch that ran: compacted iff its chunk slots fall under the
    # plan's share of the tokens, always with a pinned capacity
    skipped = round(float(s_ref.frac_skipped) * n)
    slots = min(math.ceil((n - skipped) / plan.capacity) * plan.capacity, n)
    # (at this N the survivor estimate reads every token)
    compacted = plan.compact_below is None or \
        slots < plan.compact_below * n
    assert float(s_cap.phase2_compacted) == float(compacted)
    assert float(s_cap.frac_phase2_slots) == pytest.approx(
        slots / n if compacted else 1.0, abs=1e-6)
    if plan_name == "derived":
        # the derived plan draws every token of a fresh state and only
        # the survivors of a trained one
        assert compacted == (state_name == "trained")
    assert float(s_ref.phase2_compacted) == 0.0


@pytest.mark.parametrize("n,tile", [
    (25_028_711, 8192), (23_070_469, 8192), (64 * 8192, 8192),
    (64 * 8192 + 1, 8192), (8192 * 5, 8192), (100, 8192), (2_368, 16)])
def test_derived_capacity(n, tile):
    cap = three_branch.derived_capacity(n, tile)
    assert 1 <= cap <= n
    n_chunks = -(-n // cap)
    if cap < n:
        assert cap % tile == 0
    if n >= three_branch.TARGET_CHUNKS * tile:
        # about TARGET_CHUNKS chunks at full survivorship
        assert three_branch.TARGET_CHUNKS // 2 <= n_chunks \
            <= three_branch.TARGET_CHUNKS
    else:
        assert cap == min(tile, n)


def test_build_plan_derives_or_pins(small_corpus):
    cfg = LDAConfig(n_topics=16, tile_size=16)
    plan = three_branch.build_plan(small_corpus, cfg)
    assert plan.capacity == three_branch.derived_capacity(2_368, 16) == 48
    assert plan.compact_below == three_branch.COMPACT_BELOW
    pinned = three_branch.build_plan(
        small_corpus, dataclasses.replace(cfg, survivor_capacity=200))
    assert (pinned.capacity, pinned.compact_below) == (200, None)


def test_llpt_rises_and_skip_grows(small_corpus):
    """End-to-end: LLPT increases; skip fraction grows as tokens converge
    (paper Figs 3 & 12b)."""
    cfg = LDAConfig(n_topics=16, tile_size=512, eval_every=5)
    tr = LDATrainer(small_corpus, cfg, _from_engine=True)
    state = tr.init_state()
    llpt0 = tr.evaluate(state)
    skips = []
    for i in range(20):
        state, stats = tr.step(state)
        skips.append(float(stats["frac_skipped"]))
    llpt1 = tr.evaluate(state)
    assert llpt1 > llpt0 + 0.05, (llpt0, llpt1)
    assert np.mean(skips[-5:]) > np.mean(skips[:5]), skips
    assert not np.isnan(llpt1)


def test_skip_fraction_increases_with_g(small_corpus):
    """Paper §III-B: larger g ⇒ tighter S_est ⇒ more skips."""
    cfg = LDAConfig(n_topics=16, tile_size=512)
    tr = LDATrainer(small_corpus, cfg, _from_engine=True)
    state = tr.init_state()
    for _ in range(10):
        state, _ = tr.step(state)
    key = jax.random.PRNGKey(3)
    fracs = {}
    for g in (1, 2, 4):
        plan = three_branch.Plan(g=g, tile_size=512, capacity=None)
        _, st = three_branch.sample(key, plan, tr.word_ids, tr.doc_ids,
                                    state.topics, state.D, state.W, cfg)
        fracs[g] = float(st.frac_skipped)
    assert fracs[1] <= fracs[2] + 1e-6 and fracs[2] <= fracs[4] + 1e-6, fracs


def test_two_and_three_branch_converge_to_same_llpt(small_corpus):
    """The samplers share one stationary distribution: final LLPT agrees."""
    res = {}
    for sampler in ("two_branch", "three_branch"):
        cfg = LDAConfig(n_topics=16, tile_size=512, sampler=sampler, seed=4)
        tr = LDATrainer(small_corpus, cfg, _from_engine=True)
        state = tr.init_state()
        for _ in range(25):
            state, _ = tr.step(state)
        res[sampler] = tr.evaluate(state)
    assert abs(res["two_branch"] - res["three_branch"]) < 0.15, res
