"""Shared fixtures. NOTE: no XLA_FLAGS here — the suite sees the single
real CPU device; tests that need a multi-device mesh forge one in a
subprocess."""

import pytest

from repro.lda.corpus import synthetic_lda_corpus, relabel_by_frequency, zipf_corpus
from repro.lda.model import LDAConfig


@pytest.fixture(scope="session")
def small_corpus():
    c = synthetic_lda_corpus(0, n_docs=60, n_words=80, n_topics=8,
                             mean_doc_len=40)
    c, _ = relabel_by_frequency(c)
    return c


@pytest.fixture(scope="session")
def skewed_corpus():
    c = zipf_corpus(1, n_docs=100, n_words=300, exponent=1.3, mean_doc_len=50)
    c, _ = relabel_by_frequency(c)
    return c


@pytest.fixture(scope="session")
def small_config():
    return LDAConfig(n_topics=16, tile_size=512, eval_every=5)
