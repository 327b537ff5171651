"""The program's counts read in document order from any layout the check
knows, and the recount compared with them a block of rows at a time."""

import types

import jax.numpy as jnp
import numpy as np
import pytest

from bench import harness, reference

N_DOCS, N_WORDS, K = 7, 5, 3


def _corpus(seed=0):
    rng = np.random.default_rng(seed)
    docs = np.repeat(np.arange(N_DOCS), 4)           # every document holds
    words = rng.integers(0, N_WORDS, docs.size)      # four tokens
    topics = rng.integers(0, K, docs.size)
    D = np.zeros((N_DOCS, K), np.int32)
    np.add.at(D, (docs, topics), 1)
    W = np.zeros((N_WORDS, K), np.int32)
    np.add.at(W, (words, topics), 1)
    return words, docs, topics, D, W


def _sharded(D, doc_map, docs_per_shard, rows=4):
    """Each shard's documents' rows first, then zero pad rows."""
    D_sh = np.zeros((len(doc_map), rows, K), np.int32)
    for s, ids in enumerate(doc_map):
        D_sh[s, :docs_per_shard[s]] = D[ids[:docs_per_shard[s]]]
    return D_sh


def _dist(D_sh, W, doc_map, docs_per_shard, owns=None):
    from repro.lda.distributed import DistLDAState
    state = DistLDAState(topics=None, D=D_sh, W=W, key=None, iteration=0)
    sc = types.SimpleNamespace(doc_map=np.asarray(doc_map, np.int64),
                               docs_per_shard=np.asarray(docs_per_shard),
                               owns=owns)
    trainer = types.SimpleNamespace(
        sc=sc, corpus=types.SimpleNamespace(n_docs=N_DOCS))
    return state, trainer


# a permuted map with uneven shards; pad slots map to document 0
DOC_MAP = [[5, 2, 0, 0], [0, 6, 3, 0], [1, 4, 0, 0]]
PER_SHARD = [2, 3, 2]


@pytest.fixture
def ref():
    words, docs, topics, D, W = _corpus()
    r = reference.Reference(words, docs, n_docs=N_DOCS, n_words=N_WORDS,
                            n_topics=K, alpha=0.1, beta=0.01)
    return r, topics, D, W


def test_single_state_is_taken_as_it_is(ref):
    from repro.lda.model import LDAState
    r, topics, D, W = ref
    counts = harness.document_counts(
        LDAState(topics=None, D=D, W=W, key=None, iteration=0), None)
    assert np.array_equal(counts.D, D)
    assert np.array_equal(counts.W, W) and counts.stray == 0
    assert harness.count_gap(r, topics, counts) == 0


def test_sharded_rows_read_in_document_order(ref):
    r, topics, D, W = ref
    counts = harness.document_counts(
        *_dist(_sharded(D, DOC_MAP, PER_SHARD), W, DOC_MAP, PER_SHARD))
    assert np.array_equal(counts.D, D) and np.array_equal(counts.W, W)
    assert counts.stray == 0
    assert harness.count_gap(r, topics, counts) == 0


def test_count_in_a_pad_row_is_a_gap(ref):
    r, topics, D, W = ref
    D_sh = _sharded(D, DOC_MAP, PER_SHARD)
    D_sh[0, 3, 1] = 1                      # shard 0 holds two documents
    counts = harness.document_counts(*_dist(D_sh, W, DOC_MAP, PER_SHARD))
    assert np.array_equal(counts.D, D)     # document 0's row is untouched
    assert counts.stray == 1
    assert harness.count_gap(r, topics, counts) == 1


def test_document_held_by_two_shards_is_a_gap(ref):
    r, topics, D, W = ref
    doc_map = [row[:] for row in DOC_MAP]
    doc_map[2][2] = 5                      # shard 2 lists document 5 too
    per_shard = [2, 3, 3]
    counts = harness.document_counts(
        *_dist(_sharded(D, doc_map, per_shard), W, doc_map, per_shard))
    assert counts.stray == D[5].sum() > 0
    assert harness.count_gap(r, topics, counts) == D[5].sum()


def test_moved_count_in_one_shard_is_a_gap(ref):
    r, topics, D, W = ref
    D_sh = _sharded(D, DOC_MAP, PER_SHARD)
    k = int(np.argmax(D_sh[1, 2]))
    D_sh[1, 2, k] -= 1
    D_sh[1, 2, (k + 1) % K] += 1
    counts = harness.document_counts(*_dist(D_sh, W, DOC_MAP, PER_SHARD))
    assert counts.stray == 0
    assert harness.count_gap(r, topics, counts) == 2


def test_other_layouts_are_refused_by_name(ref):
    _, _, D, W = ref
    from repro.lda.model import SparseLDAState
    packed = SparseLDAState(*[None] * len(SparseLDAState._fields))
    with pytest.raises(TypeError, match="SparseLDAState"):
        harness.document_counts(packed, None)
    replicated = _dist(_sharded(D, DOC_MAP, PER_SHARD), W, DOC_MAP,
                       PER_SHARD, owns=np.ones((3, 4), np.int32))
    with pytest.raises(TypeError, match="DistLDAState"):
        harness.document_counts(*replicated)


def test_w_that_is_not_replicated_is_refused(ref):
    _, _, D, W = ref
    with pytest.raises(ValueError):
        harness.document_counts(*_dist(_sharded(D, DOC_MAP, PER_SHARD),
                                       W[None], DOC_MAP, PER_SHARD))


@pytest.mark.parametrize("block_rows", [1, 64, 1003, 5000])
def test_blocked_gap_equals_eager(block_rows):
    rng = np.random.default_rng(block_rows)
    a = rng.integers(0, 50, (1003, 5)).astype(np.int32)
    b = rng.integers(0, 50, (1003, 5)).astype(np.int32)
    eager = int(jnp.sum(jnp.abs(jnp.asarray(a) - jnp.asarray(b))))
    assert reference.abs_gap(jnp.asarray(a), b, block_rows) == eager
    assert reference.abs_gap(jnp.asarray(a), a, block_rows) == 0


def test_blocked_gap_refuses_another_shape():
    a = jnp.zeros((10, 3), jnp.int32)
    with pytest.raises(ValueError):
        reference.abs_gap(a, np.zeros((4, 10, 3), np.int32), 4)
