"""The generator keeps the shape its configuration states."""

import copy
import json
import pathlib

import numpy as np
import pytest

from bench import corpus_gen

CONFIGS = pathlib.Path(__file__).resolve().parents[1] / "configs"


def _cfg(name, **over):
    cfg = json.loads((CONFIGS / f"{name}.json").read_text())
    cfg = copy.deepcopy(cfg)
    cfg.update(over)
    return cfg


@pytest.mark.parametrize("name", ["nytimes-k1k", "pubmed-k1k"])
def test_published_shape(name):
    """At the published V and K, with fewer documents: Zipf head, mean
    length, token count and the paper's token-list layout."""
    cfg = _cfg(name, n_docs=3000)
    g = corpus_gen.generate(cfg, 2**31 + 17)
    assert g.n_words == cfg["published"]["n_words"]
    assert g.n_topics == cfg["published"]["n_topics"]
    assert g.n_tokens == int(corpus_gen.doc_lengths(cfg).sum())
    assert g.doc_lengths.mean() == pytest.approx(cfg["mean_doc_len"],
                                                 rel=0.03)
    assert np.all(np.diff(g.word_ids) >= 0)
    assert np.array_equal(np.bincount(g.word_ids, minlength=g.n_words),
                          g.word_counts)
    assert np.all(np.diff(g.word_counts) <= 0)
    assert np.array_equal(np.bincount(g.doc_ids, minlength=g.n_docs),
                          g.doc_lengths)
    r = np.arange(10, 300)
    slope = np.polyfit(np.log(r + 1), np.log(g.word_counts[r]), 1)[0]
    assert slope == pytest.approx(-cfg["zipf_exponent"], abs=0.08)
    assert g.topics.min() >= 0 and g.topics.max() < g.n_topics


@pytest.mark.parametrize("name", ["nytimes-k1k", "pubmed-k1k"])
def test_full_size_token_count(name):
    cfg = _cfg(name)
    lens = corpus_gen.doc_lengths(cfg)
    assert lens.shape == (cfg["n_docs"],)
    assert lens.sum() == pytest.approx(cfg["n_docs"] * cfg["mean_doc_len"],
                                       rel=0.01)


def test_seed_changes_order_not_work():
    cfg = _cfg("nytimes-k1k", n_docs=500, n_words=5000, n_topics=64)
    a = corpus_gen.generate(cfg, 7)
    b = corpus_gen.generate(cfg, 7)
    c = corpus_gen.generate(cfg, 2**40 + 7)
    assert np.array_equal(a.word_ids, b.word_ids)
    assert np.array_equal(a.doc_ids, b.doc_ids)
    assert np.array_equal(a.topics, b.topics)
    assert a.n_tokens == c.n_tokens
    assert np.array_equal(np.sort(a.doc_lengths), np.sort(c.doc_lengths))
    assert not np.array_equal(a.doc_ids, c.doc_ids)


def test_planted_topics_are_concentrated():
    """Most of a word's tokens sit in its own topic: the structure that
    makes a converged state skip."""
    cfg = _cfg("nytimes-k1k", n_docs=500, n_words=5000, n_topics=64)
    g = corpus_gen.generate(cfg, 3)
    top = np.zeros(g.n_words)
    for w in range(20):
        zs = g.topics[g.word_ids == w]
        top[w] = np.bincount(zs).max() / zs.size
    assert np.mean(top[:20]) > 0.6
