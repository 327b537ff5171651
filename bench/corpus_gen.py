"""The benchmark's one corpus generator: a planted-topic LDA corpus at a
published dataset's shape, made on the host from ``--seed`` in bulk.

The generative process, with ``K`` planted topics over a vocabulary of
``V`` words ranked by a Zipf law ``p(r) ∝ r^-s``:

* every word rank ``r`` is owned by topic ``r mod K`` (``word_topic_rule``);
  topic ``k``'s own distribution is the Zipf law restricted to its words;
* topic ``k``'s prevalence is the Zipf mass of its own words, so the mixture
  of the topics' own distributions is exactly the Zipf law;
* a topic's word distribution is its own distribution with probability
  ``1 - background_share`` and the whole Zipf law otherwise, which leaves
  the word marginal Zipf and puts every word in every topic;
* a document draws ``topics_per_doc`` topics by prevalence with weights from
  a symmetric Dirichlet(``doc_topic_conc``); each token draws its topic from
  those weights and its word from that topic;
* document lengths are log-normal (``doc_len_sigma``) around the published
  mean. The set of lengths comes from ``length_seed`` and is the same for
  every run seed, which only permutes it: every seed does the same amount of
  work.

Words are then relabelled by their realised token count (most frequent
first) and tokens sorted by word, stable in document order: the token list
``T`` of the paper's preprocessing (EZLDA §IV-B), handed to the engine as is.
"""

from __future__ import annotations

import dataclasses
import time

import numpy as np


@dataclasses.dataclass(frozen=True)
class Generated:
    word_ids: np.ndarray      # (N,) int32, sorted ascending
    doc_ids: np.ndarray       # (N,) int32
    topics: np.ndarray        # (N,) int32, the planted topic of each token
    doc_lengths: np.ndarray   # (M,) int64
    word_counts: np.ndarray   # (V,) int64, non-increasing
    n_words: int
    n_docs: int
    n_topics: int
    seconds: float            # host seconds the generation took

    @property
    def n_tokens(self) -> int:
        return int(self.word_ids.shape[0])


def rng_for(seed: int) -> np.random.Generator:
    """A generator for any whole-number seed, negative or beyond 64 bits."""
    return np.random.default_rng(np.random.SeedSequence(int(seed) % 2**64))


def zipf_probs(n_words: int, exponent: float) -> np.ndarray:
    p = np.arange(1, n_words + 1, dtype=np.float64) ** (-exponent)
    return p / p.sum()


def doc_lengths(cfg: dict) -> np.ndarray:
    """The configuration's fixed multiset of document lengths."""
    a = cfg["assumed"]
    sigma = float(a["doc_len_sigma"])
    mu = np.log(cfg["mean_doc_len"]) - sigma ** 2 / 2
    rng = rng_for(a["length_seed"])
    lens = np.rint(rng.lognormal(mu, sigma, size=cfg["n_docs"]))
    return np.maximum(lens, 1).astype(np.int64)


def generate(cfg: dict, seed: int) -> Generated:
    t0 = time.perf_counter()
    a = cfg["assumed"]
    V, M, K = int(cfg["n_words"]), int(cfg["n_docs"]), int(cfg["n_topics"])
    T = int(a["topics_per_doc"])
    rng = rng_for(seed)

    lens = rng.permutation(doc_lengths(cfg))
    n = int(lens.sum())
    doc = np.repeat(np.arange(M, dtype=np.int32), lens)

    p = zipf_probs(V, float(cfg["zipf_exponent"]))
    owner = np.arange(V) % K                                  # word_topic_rule
    prevalence = np.bincount(owner, weights=p, minlength=K)

    # each document's topics and their weights
    doc_topics = rng.choice(K, size=(M, T), p=prevalence).astype(np.int32)
    w = rng.gamma(float(a["doc_topic_conc"]), size=(M, T)) + 1e-12
    cw = np.cumsum(w / w.sum(axis=1, keepdims=True), axis=1)
    cw[:, -1] = 1.0
    flat = (cw + np.arange(M)[:, None]).ravel()
    slot = np.searchsorted(flat, doc + rng.random(n), side="right") \
        - doc.astype(np.int64) * T
    z = doc_topics[doc, np.minimum(slot, T - 1)]

    # the token's word: its topic's own words, or the whole Zipf law
    by_topic = np.argsort(owner, kind="stable")               # ranks by topic
    csum = np.cumsum(p[by_topic])
    ends = np.cumsum(np.bincount(owner, minlength=K))          # block ends
    start_mass = np.concatenate([[0.0], csum[ends[:-1] - 1]])
    topic_of = owner[by_topic]
    own_cdf = topic_of + (csum - start_mass[topic_of]) / prevalence[topic_of]
    idx = np.searchsorted(own_cdf, z + rng.random(n), side="right")
    idx = np.minimum(idx, ends[z] - 1)
    rank = by_topic[idx]
    bg = rng.random(n) < float(a["background_share"])
    rank[bg] = np.minimum(np.searchsorted(np.cumsum(p), rng.random(int(bg.sum())),
                                          side="right"), V - 1)

    # relabel by realised frequency, then the word-sorted token list T
    counts = np.bincount(rank, minlength=V)
    order = np.argsort(-counts, kind="stable")
    new_id = np.empty(V, np.int32)
    new_id[order] = np.arange(V, dtype=np.int32)
    word = new_id[rank]
    perm = np.argsort(word, kind="stable")
    return Generated(
        word_ids=word[perm], doc_ids=doc[perm],
        topics=z[perm].astype(np.int32), doc_lengths=lens,
        word_counts=counts[order].astype(np.int64),
        n_words=V, n_docs=M, n_topics=K,
        seconds=time.perf_counter() - t0)
