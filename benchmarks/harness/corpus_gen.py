"""The one generator of fit traffic: a bag-of-words corpus drawn from
LDA's own generative model, as CSR arrays, from a traffic file and a seed.

A traffic file (`benchmarks/traffic/<name>.json`) holds only parameters;
this module is the code that reads them.  Three of its laws are FITTED to
a day realised by the repo's own generators through the repo's own `pre`
and `corpus` stages (the traffic file's `fit` group records the call, the
histogram it gave and the histogram this generator gives):

- `length`: distinct words per document, as a table of quantiles; the
  documents of a run are the deterministic quantiles of that table, so
  every seed has the same multiset of sizes (the same buckets, batches and
  ragged tails), dealt to the documents in another order.
- tokens per document: a document of L distinct words is what n(L) events
  leave, where n(L) inverts the expected number of distinct words among n
  draws from the word law (times `oversample`).  Counts are the
  multiplicities (>= 1, clipped at `count_cap` so that every count is
  exact in bfloat16): about 1 in a small document, heavy in a hot one, as
  in the source.
- word law: p0(w) ~ 1 / (rank + zipf_shift) ** zipf_s, fitted to the
  source's share of (document, word) pairs by word rank.

What the source's days do not have is topics (their features are drawn
independently).  The generator can plant them: beta_k ~ Dirichlet(topic_eta
* V * p0) (the marginal word law stays p0), theta_d ~
Dirichlet(doc_topic_alpha); a document's draws come from sum_k theta_dk
beta_k; it keeps its L most frequent distinct words, and one whose draws
hold fewer than L is topped up with distinct words from p0, count 1: the
rare words every real document carries.  Smaller topic_eta = sharper
topics.  Where EM can learn the topics its path is chaotic (the number of
iterations swings by seed, PERF.md section 2), so the cells set them too
weak to find, as in the source.

Everything but the multiset of lengths is drawn from the run's seed.
"""

from __future__ import annotations

from dataclasses import dataclass

import numpy as np

@dataclass
class CsrCorpus:
    """Plain arrays; the job turns them into whatever the program takes."""

    num_terms: int
    doc_ptr: np.ndarray    # [D+1] int64
    word_idx: np.ndarray   # [NNZ] int32, distinct within a document
    counts: np.ndarray     # [NNZ] int32, >= 1

    @property
    def num_docs(self) -> int:
        return len(self.doc_ptr) - 1

    def doc_lengths(self) -> np.ndarray:
        return np.diff(self.doc_ptr)

    def doc_tokens(self) -> np.ndarray:
        return np.add.reduceat(self.counts, self.doc_ptr[:-1])


def length_multiset(num_docs: int, law: dict, num_terms: int) -> np.ndarray:
    """Deterministic quantiles of the table law["quantiles"] = [[p, L], ...]
    (p ascending from 0 to 1), log-linear between its points.  Sorted
    ascending, at least 1 and at most `num_terms`."""
    ps, ls = (np.asarray(c, np.float64) for c in zip(*law["quantiles"]))
    u = (np.arange(num_docs, dtype=np.float64) + 0.5) / num_docs
    lengths = np.floor(np.exp(np.interp(u, ps, np.log(ls))) + 1e-9)
    return np.clip(lengths.astype(np.int64), 1, num_terms)


def draws_for_lengths(lengths: np.ndarray, p0: np.ndarray,
                      oversample: float) -> np.ndarray:
    """n(L): how many draws from p0 leave L distinct words on average
    (E[distinct] = sum_w 1 - exp(-n p_w), inverted on a grid), times
    `oversample`; never fewer than L."""
    grid = np.unique(np.round(np.geomspace(1, 1e9, 400)))
    distinct = np.array([-np.expm1(-n * p0).sum() for n in grid])
    n = np.exp(np.interp(np.log(np.minimum(lengths, distinct[-1] * 0.999)),
                         np.log(distinct), np.log(grid)))
    return np.maximum(np.ceil(n * oversample).astype(np.int64), lengths)


def planted_topics(rng: np.random.Generator, num_topics: int, num_terms: int,
                   p: dict) -> tuple[np.ndarray, np.ndarray]:
    """(beta [K, V] float64 rows summing to 1, base law p0 [V])."""
    ranks = rng.permutation(num_terms).astype(np.float64)
    p0 = 1.0 / (ranks + p["zipf_shift"]) ** p["zipf_s"]
    p0 /= p0.sum()
    conc = p["topic_eta"] * num_terms * p0
    beta = rng.standard_gamma(np.broadcast_to(conc, (num_topics, num_terms)))
    beta = np.maximum(beta, 1e-300)
    beta /= beta.sum(-1, keepdims=True)
    return beta, p0


def _draw_words(rng, cdf: np.ndarray, n: int) -> np.ndarray:
    """`n` inverse-CDF draws from one cumulative word law."""
    w = np.searchsorted(cdf, rng.random(n) * cdf[-1], side="right")
    return np.minimum(w, len(cdf) - 1)


def _first_n_per_doc(d: np.ndarray, rank_key: np.ndarray,
                     keep: np.ndarray) -> np.ndarray:
    """Indices, sorted by document then `rank_key` (< 2**40, ascending),
    of at most keep[doc] entries per document."""
    order = np.argsort(d * (1 << 40) + rank_key)
    ds = d[order]
    pos = np.arange(len(ds)) - np.searchsorted(ds, np.arange(len(keep)))[ds]
    return order[pos < keep[ds]]


def _distinct_by_count(doc: np.ndarray, word: np.ndarray, num_terms: int,
                       keep: np.ndarray, rng) -> tuple:
    """Distinct (doc, word) pairs with multiplicities, at most keep[doc]
    per document, the most frequent first (ties by the seed)."""
    key = np.sort(doc * num_terms + word)
    first = np.flatnonzero(np.r_[True, key[1:] != key[:-1]])
    mult = np.diff(np.r_[first, len(key)])
    d, w = np.divmod(key[first], num_terms)
    rank = ((1 << 19) - np.minimum(mult, (1 << 19) - 1)) * (1 << 20)
    sel = _first_n_per_doc(d, rank + rng.integers(0, 1 << 20, len(d)), keep)
    return d[sel], w[sel], mult[sel]


def make_corpus(traffic: dict, num_terms: int, seed: int) -> CsrCorpus:
    """The corpus of one run.  `traffic` is the parsed traffic file."""
    p = traffic["corpus"]
    num_docs = int(traffic["num_docs"])
    rng = np.random.default_rng(int(seed))   # any whole number
    lengths = length_multiset(num_docs, p["length"], num_terms)
    lengths = lengths[rng.permutation(num_docs)]

    k = int(p["planted_topics"])
    beta, p0 = planted_topics(rng, k, num_terms, p)
    theta = rng.standard_gamma(p["doc_topic_alpha"], (num_docs, k))
    theta = np.maximum(theta, 1e-300)
    theta /= theta.sum(-1, keepdims=True)

    # Tokens: how many of a document's draws each topic gets, then that
    # many words from the topic's law, topic by topic.
    per_topic = rng.multinomial(
        draws_for_lengths(lengths, p0, float(p["oversample"])), theta)
    docs = np.arange(num_docs)
    doc_of = np.concatenate(
        [np.repeat(docs, per_topic[:, j]) for j in range(k)])
    words = np.concatenate(
        [_draw_words(rng, np.cumsum(beta[j]), int(per_topic[:, j].sum()))
         for j in range(k)])
    d, w, c = _distinct_by_count(doc_of, words, num_terms, lengths, rng)

    # Top up documents whose draws held too few distinct words.
    base_cdf = np.cumsum(p0)
    for _ in range(64):
        need = lengths - np.bincount(d, minlength=num_docs)
        short = np.flatnonzero(need > 0)
        if not len(short):
            break
        extra_doc = np.repeat(short, 2 * need[short] + 2)
        key = np.unique(extra_doc * num_terms
                        + _draw_words(rng, base_cdf, len(extra_doc)))
        held = need[d] > 0
        key = key[~np.isin(key, d[held] * num_terms + w[held])]
        ed, ew = np.divmod(key, num_terms)
        sel = _first_n_per_doc(ed, rng.integers(0, 1 << 20, len(ed)), need)
        d = np.r_[d, ed[sel]]
        w = np.r_[w, ew[sel]]
        c = np.r_[c, np.ones(len(sel), c.dtype)]
    else:
        raise ValueError("corpus generator: could not fill every document "
                         "to its length; the word law is too narrow")

    # Words inside a document in a random order (as first seen in a day).
    order = np.argsort(d * (1 << 40) + rng.integers(0, 1 << 40, len(d)))
    d, w, c = d[order], w[order], c[order]
    doc_ptr = np.searchsorted(d, np.arange(num_docs + 1)).astype(np.int64)
    if not np.array_equal(np.diff(doc_ptr), lengths):
        raise AssertionError("corpus generator: lengths do not match")
    return CsrCorpus(
        num_terms=num_terms,
        doc_ptr=doc_ptr,
        word_idx=w.astype(np.int32),
        counts=np.minimum(c, int(p["count_cap"])).astype(np.int32),
    )
