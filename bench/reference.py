"""The plain reference: collapsed-Gibbs LDA in straightforward ``jax.numpy``.

It imports nothing of the program. From a token list, a topic assignment and
an RNG key it computes what one iteration must produce:

* the counts ``D[d][k]``, ``W[v][k]`` and ``colsum[k]`` by one scatter-add;
* each token's draw from ``p(k) ∝ (D[d][k] + α)·Ŵ[v][k]`` with
  ``Ŵ[v][k] = (W[v][k] + β) / (colsum[k] + V·β)``, the counts of the
  previous iteration (the token's own assignment included), made by inverse
  CDF with the token's uniform ``u`` over the topics ordered as the sampler
  defines its draw: the word's most probable topic ``K1`` first, then the
  others in ascending order. The uniforms are ``uniform(sub, (N,))`` with
  ``key, sub = split(key)`` per iteration: the program's documented
  same-key, same-draw guarantee, which every sampling path keeps;
* the log-likelihood per token (EZLDA Eq 5).

``dtype`` is float32, as the configurations state; ``jnp.bfloat16`` gives the
control, computed a precision lower, which the comparison must reject.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

BLOCK = 8192          # tokens per block: one (BLOCK, K) row gather at a time
ROUNDING = 1e-5      # share of a token's total mass: rounding room at a boundary
GAP_ELEMENTS = 1 << 26  # the program's counts on the device at a time (256 MiB)


@functools.partial(jax.jit, static_argnames=("n_docs", "n_words", "n_topics"))
def counts(word_ids, doc_ids, topics, *, n_docs, n_words, n_topics):
    D = jnp.zeros((n_docs, n_topics), jnp.int32).at[doc_ids, topics].add(1)
    W = jnp.zeros((n_words, n_topics), jnp.int32).at[word_ids, topics].add(1)
    return D, W, jnp.sum(W, axis=0)


def _cumsum(x, dtype):
    if dtype == jnp.float32:
        return jnp.cumsum(x, axis=-1)
    # every partial sum rounded to the control's precision
    return jax.lax.associative_scan(lambda a, b: (a + b).astype(dtype), x,
                                    axis=-1)


def _blocks(fn, *arrays):
    """``fn`` over consecutive BLOCK-token slices (the tail padded)."""
    n = arrays[0].shape[0]
    pad = (-n) % BLOCK
    xs = tuple(jnp.pad(a, (0, pad)).reshape(-1, BLOCK) for a in arrays)
    out = jax.lax.map(lambda args: fn(*args), xs)
    return out.reshape(-1, *out.shape[2:])[:n]


@functools.partial(jax.jit, static_argnames=("alpha", "beta", "dtype"))
def draw(u, words, docs, D, W, colsum, *, alpha, beta, dtype=jnp.float32):
    """The topic each listed token draws with its uniform ``u``, and the
    topics it draws with ``u·total`` moved by ``±ROUNDING·total``: a draw
    that lands this close to a CDF boundary may fall on either side of it
    when the same sums are added in another order."""
    V = W.shape[0]
    col = colsum.astype(dtype) + jnp.asarray(V * beta, dtype)

    def block(u_b, w_b, d_b):
        w_hat = (W[w_b].astype(dtype) + jnp.asarray(beta, dtype)) / col
        mass = (D[d_b].astype(dtype) + jnp.asarray(alpha, dtype)) * w_hat
        k1 = jnp.argmax(w_hat, axis=-1)
        m = jnp.take_along_axis(mass, k1[:, None], axis=-1)[:, 0]
        rest = jnp.where(jnp.arange(mass.shape[-1]) == k1[:, None],
                         jnp.zeros((), dtype), mass)
        cum = _cumsum(rest, dtype)
        total = m + cum[:, -1]
        x = u_b.astype(dtype) * total

        def topic(x):
            k_c = jnp.sum(cum <= (x - m)[:, None], axis=-1)
            k_c = jnp.minimum(k_c, mass.shape[-1] - 1)
            return jnp.where(x < m, k1, k_c).astype(jnp.int32)

        room = jnp.asarray(ROUNDING, dtype) * total
        return jnp.stack([topic(x), topic(x - room), topic(x + room)], -1)

    return _blocks(block, u, words, docs)


@functools.partial(jax.jit, static_argnames=("alpha", "beta", "dtype"))
def token_loglik(words, docs, D, W, colsum, *, alpha, beta,
                 dtype=jnp.float32):
    """log2 Σ_k θ[d][k]·φ[v][k] for every token (EZLDA Eq 5's summand)."""
    V = W.shape[0]
    K = D.shape[1]
    doc_len = jnp.sum(D, axis=-1).astype(dtype)
    col = colsum.astype(dtype) + jnp.asarray(V * beta, dtype)

    def block(w_b, d_b):
        theta = (D[d_b].astype(dtype) + jnp.asarray(alpha, dtype)) \
            / (doc_len[d_b][:, None] + jnp.asarray(K * alpha, dtype))
        phi = (W[w_b].astype(dtype) + jnp.asarray(beta, dtype)) / col
        return jnp.log2(jnp.sum(theta * phi, axis=-1, dtype=dtype)
                        ).astype(jnp.float32)

    return _blocks(block, words, docs)


@jax.jit
def _block_gap(ref, start, block):
    part = jax.lax.dynamic_slice_in_dim(ref, start, block.shape[0])
    return jnp.sum(jnp.abs(part - block))


def abs_gap(ref, prog: np.ndarray, block_rows: int) -> int:
    """Σ|ref - prog| for a device array ``ref`` and a host array ``prog``
    of its shape, sent to the device ``block_rows`` rows at a time, so the
    device holds ``ref`` and one block of ``prog``."""
    prog = np.asarray(prog)
    if prog.shape != ref.shape:
        raise ValueError(f"the program's counts have shape {prog.shape}, "
                         f"the recount {ref.shape}")
    total = 0
    for start in range(0, prog.shape[0], block_rows):
        block = jnp.asarray(prog[start:start + block_rows])
        total += int(_block_gap(ref, start, block))
        del block
    return total


def step_keys(key_data: np.ndarray):
    """(next key data, uniform key) of one iteration from a key's data."""
    nxt, sub = jax.random.split(jnp.asarray(key_data, jnp.uint32))
    return np.asarray(nxt), sub


class Reference:
    """One corpus on the device, and the counts of one assignment at a time."""

    def __init__(self, word_ids, doc_ids, *, n_docs, n_words, n_topics,
                 alpha, beta):
        self.words = jnp.asarray(word_ids, jnp.int32)
        self.docs = jnp.asarray(doc_ids, jnp.int32)
        self.shape = dict(n_docs=n_docs, n_words=n_words, n_topics=n_topics)
        self.alpha, self.beta = float(alpha), float(beta)
        self.n = int(self.words.shape[0])

    def counts(self, topics):
        return counts(self.words, self.docs, jnp.asarray(topics, jnp.int32),
                      **self.shape)

    def next_topics(self, topics, key_data, sample=None, dtype=jnp.float32):
        """(draws, next key): the draws in the iteration after ``topics``
        under ``key_data`` of every token, or of the ``sample``'s tokens
        alone, as (n, 3) topics on the device: the draw and its two
        neighbours within rounding room (see ``draw``)."""
        D, W, colsum = self.counts(topics)
        nxt, sub = step_keys(key_data)
        u = jax.random.uniform(sub, (self.n,), dtype=jnp.float32)
        words, docs = self.words, self.docs
        if sample is not None:
            idx = jnp.asarray(sample, jnp.int32)
            u, words, docs = u[idx], words[idx], docs[idx]
        z = draw(u, words, docs, D, W, colsum, alpha=self.alpha,
                 beta=self.beta, dtype=dtype)
        return z, nxt

    def llpt(self, topics, dtype=jnp.float32) -> float:
        D, W, colsum = self.counts(topics)
        ll = token_loglik(self.words, self.docs, D, W, colsum,
                          alpha=self.alpha, beta=self.beta, dtype=dtype)
        return float(np.mean(np.asarray(ll, np.float64)))

    def count_gap(self, topics, D_prog, W_prog) -> int:
        """Σ|ΔD| + Σ|ΔW| between the program's counts and the recount."""
        D, W, _ = self.counts(topics)
        rows = max(1, GAP_ELEMENTS // self.shape["n_topics"])
        return abs_gap(D, D_prog, rows) + abs_gap(W, W_prog, rows)


def mismatch(program_topics, draws) -> float:
    """Share of tokens whose topic is none of the reference's draws."""
    p = np.asarray(program_topics)[:, None]
    return float(np.mean(~np.any(p == draws, axis=1)))
