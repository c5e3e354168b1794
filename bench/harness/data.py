"""The benchmark's own data: a collection and query logs made from a seed.

The generators follow ``repro.retrieval.corpus`` (its copy lives here so
that a change to the program cannot change the benchmark's data):

* document lengths are log-normal, terms a Zipf draw over the vocabulary,
  aggregated into a doc-major bag-of-words (``doc_ids``, ``term_ids``,
  ``counts``, ``doc_len``);
* a query is a run of words drawn as the collection's text is drawn, by
  each term's frequency in the collection; its length in words is 1 plus a
  geometric draw (mean ``mean_words``) and at most ``max_len``.  The
  ``stopwords`` most frequent terms are dropped, as the index's analyzer
  drops its stop list, and the rest kept once each; a query left with no
  term is drawn again.

``query_pool`` draws queries with no row repeated and none equal to a
row of an ``exclude`` log, so a window never sends the same query twice
and never one the cascade was trained on.
"""

from __future__ import annotations

import dataclasses

import numpy as np

__all__ = ["Collection", "make_collection", "make_queries", "query_pool",
           "term_freq"]


@dataclasses.dataclass
class Collection:
    """Bag-of-words collection in doc-major COO form."""

    n_docs: int
    vocab: int
    doc_ids: np.ndarray    # (nnz,) int32, sorted
    term_ids: np.ndarray   # (nnz,) int32
    counts: np.ndarray     # (nnz,) int32
    doc_len: np.ndarray    # (n_docs,) int32


def make_collection(n_docs: int, vocab: int, mean_doc_len: float,
                    sigma_doc_len: float, zipf_s: float,
                    seed: int) -> Collection:
    rng = np.random.default_rng(seed)
    mu = np.log(mean_doc_len) - 0.5 * sigma_doc_len ** 2
    doc_len = np.maximum(
        rng.lognormal(mu, sigma_doc_len, n_docs).astype(np.int64), 8)
    ranks = np.arange(1, vocab + 1, dtype=np.float64)
    probs = ranks ** (-zipf_s)
    probs /= probs.sum()
    tokens = rng.choice(vocab, size=int(doc_len.sum()), p=probs)
    doc_of_token = np.repeat(np.arange(n_docs, dtype=np.int64), doc_len)
    key = doc_of_token * vocab + tokens.astype(np.int64)
    uniq, counts = np.unique(key, return_counts=True)
    return Collection(n_docs=n_docs, vocab=vocab,
                      doc_ids=(uniq // vocab).astype(np.int32),
                      term_ids=(uniq % vocab).astype(np.int32),
                      counts=counts.astype(np.int32),
                      doc_len=doc_len.astype(np.int32))


def term_freq(col: Collection) -> np.ndarray:
    """How often every term of the vocabulary occurs in the collection."""
    return np.bincount(col.term_ids, weights=col.counts,
                       minlength=col.vocab).astype(np.int64)


def make_queries(freq: np.ndarray, n_queries: int, rng: np.random.Generator,
                 *, max_len: int, mean_words: float,
                 stopwords: int) -> np.ndarray:
    """(n_queries, max_len) int32 query rows, -1 padded, terms ascending."""
    order = np.argsort(-freq, kind="stable")
    stop = np.zeros(len(freq), bool)
    stop[order[:stopwords]] = True
    cdf = np.cumsum(freq.astype(np.float64))
    cdf /= cdf[-1]
    terms = np.full((n_queries, max_len), -1, np.int32)
    todo = np.arange(n_queries)
    while len(todo):
        words = np.minimum(1 + rng.geometric(1.0 / (mean_words - 1.0),
                                             len(todo)), max_len)
        flat = np.minimum(np.searchsorted(cdf, rng.random(int(words.sum())),
                                          side="right"), len(freq) - 1)
        left, pos = [], 0
        for i, n in zip(todo, words):
            w = flat[pos:pos + n]
            pos += n
            u = np.unique(w[~stop[w]])
            if len(u) == 0:
                left.append(i)
            terms[i, :len(u)] = u
        todo = np.asarray(left, np.int64)
    return terms


def query_pool(freq: np.ndarray, n_queries: int, seed: int, *,
               exclude: np.ndarray | None = None, **law) -> np.ndarray:
    """``n_queries`` distinct query rows drawn from ``seed``'s own stream,
    none of them a row of ``exclude``."""
    rng = np.random.default_rng([int(seed), 0x9E3779B9])
    seen = set() if exclude is None else {r.tobytes() for r in exclude}
    out: list[np.ndarray] = []
    while len(out) < n_queries:
        for row in make_queries(freq, n_queries, rng, **law):
            key = row.tobytes()
            if key not in seen:
                seen.add(key)
                out.append(row)
                if len(out) == n_queries:
                    break
    return np.stack(out)
