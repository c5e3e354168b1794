"""Gold-standard runs — the training signal that replaces relevance judgments.

Two gold standards, exactly as in the paper (Section 4):

  * for tuning k: a *second-stage ranker* run over a deep candidate pool
    (the paper uses the uogTRMQdph40 TREC run; offline we use a seeded
    multi-signal reranker that is deliberately different from the stage-1
    BM25 impact scorer — see ``second_stage_scores``).  The candidate run
    at cutoff k is the same reranker restricted to the stage-1 top-k pool,
    so MED(A, B_k) measures exactly "what did the smaller pool cost the
    second stage".
  * for tuning rho: exhaustive score-at-a-time evaluation (the exact
    ranking); the candidate run is the anytime ranking at rho.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.retrieval import jass

__all__ = [
    "second_stage_scores",
    "second_stage_mix",
    "pool_stage2_scores",
    "rerank_pool",
    "rerank_scored",
    "gold_run_k",
    "candidate_run_k",
    "gold_run_rho",
    "candidate_run_rho",
]


#: grid of the second-stage mixture's weighted terms (see second_stage_mix)
_GRID = float(1 << 20)


def _hash_noise(doc_ids: jnp.ndarray, qid: jnp.ndarray, seed: int) -> jnp.ndarray:
    """Deterministic per-(query, doc) pseudo-feature in [0, 1) — stands in
    for the second stage's non-lexical ML features (links, clicks, ...)."""
    h = (doc_ids.astype(jnp.uint32) * jnp.uint32(2654435761)
         ^ (qid.astype(jnp.uint32) * jnp.uint32(40503))
         ^ jnp.uint32(seed))
    h = (h ^ (h >> 15)) * jnp.uint32(2246822519)
    h = h ^ (h >> 13)
    return (h & jnp.uint32(0xFFFF)).astype(jnp.float32) / 65536.0


def second_stage_mix(acc_bm25: jnp.ndarray, acc_lm: jnp.ndarray,
                     acc_tfidf: jnp.ndarray, bounds, doc_len: jnp.ndarray,
                     qids: jnp.ndarray, doc_ids: jnp.ndarray, *,
                     seed: int = 11,
                     noise_weight: float = 0.35) -> jnp.ndarray:
    """The second-stage mixture with explicit normalization bounds.

    ``bounds`` is ((lo, hi), ...) per accumulator, each (Q, 1) — the
    per-query min/max over the *full* doc axis.  Split out so the
    mesh-sharded engine can compute bounds with pmin/pmax collectives over
    its doc shards and still run bit-identical mixing arithmetic on each
    local (Q, width) block, and so ``pool_stage2_scores`` can mix only a
    (Q, K) block of pool members.  ``doc_ids`` are the global ids of the
    block's columns (the noise hash keys on them) and ``doc_len`` their
    lengths: both (width,) where every row shares its columns, or (Q, K)
    where each row has its own.

    Each weighted term is rounded to a multiple of ``2**-20`` before the
    terms are added, so the sum (below 2) is exact in float32.  Without
    that, a compiler that contracts ``w * x + y`` into a fused
    multiply-add rounds the score differently depending on how the
    program around it was fused — eager vs jitted, batch vs slot group —
    and two paths that must agree bit for bit disagree on near-ties.
    """

    def norm(x, lo, hi):
        return (x - lo) / jnp.maximum(hi - lo, 1e-9)

    def term(w, x):
        return jnp.round(w * x * _GRID) / _GRID

    (b_lo, b_hi), (l_lo, l_hi), (t_lo, t_hi) = bounds
    prior = 1.0 / jnp.log(2.0 + doc_len.astype(jnp.float32))
    noise = _hash_noise(doc_ids, qids[:, None], seed)
    return (term(0.45, norm(acc_bm25, b_lo, b_hi))
            + term(0.25, norm(acc_lm, l_lo, l_hi))
            + term(0.15, norm(acc_tfidf, t_lo, t_hi))
            + term(0.05, prior) + term(noise_weight, noise))


def second_stage_scores(acc_bm25: jnp.ndarray, acc_lm: jnp.ndarray,
                        acc_tfidf: jnp.ndarray, doc_len: jnp.ndarray,
                        qids: jnp.ndarray, *, seed: int = 11,
                        noise_weight: float = 0.35) -> jnp.ndarray:
    """Dense second-stage scores for all docs of a query batch.

    acc_*: (Q, n_docs) per-scorer stage-1 accumulators; doc_len: (n_docs,).
    The mixture + interaction noise makes the induced ranking correlated
    with — but distinct from — any single stage-1 scorer, mirroring the
    gold run's relationship to the BM25 candidate run in the paper.

    The oracle form: labelling (``core.experiment``), the per-bucket
    reference path (``pipeline.serve_batch_reference``) and the sharded
    engine score every doc; the single-chip serving programs score only
    the pool with ``pool_stage2_scores``, which equals this bit for bit.
    """
    n_docs = acc_bm25.shape[-1]

    def bound(x):
        return (jnp.min(x, axis=-1, keepdims=True),
                jnp.max(x, axis=-1, keepdims=True))

    return second_stage_mix(
        acc_bm25, acc_lm, acc_tfidf,
        (bound(acc_bm25), bound(acc_lm), bound(acc_tfidf)),
        doc_len, qids, jnp.arange(n_docs),
        seed=seed, noise_weight=noise_weight)


def pool_stage2_scores(sdocs: jnp.ndarray, s3: jnp.ndarray,
                       pool: jnp.ndarray, doc_len: jnp.ndarray,
                       qids: jnp.ndarray, *, n_docs: int, cap: int,
                       seed: int = 11,
                       noise_weight: float = 0.35) -> jnp.ndarray:
    """Second-stage scores of the pool's docs alone: (Q, K).

    Equal bit for bit to ``second_stage_scores`` over
    ``jass.scorer_accumulators(sdocs, s3, n_docs)``, read at each row's
    pool members (``-1`` entries score as doc 0 and are for the caller to
    mask), with no ``n_docs``-wide array.  ``sdocs``/``s3`` are the
    gathered score postings (``jass.gather_score_streams`` at ``cap``).

    The per-doc sums come from ``jass.scorer_sums``.  The bounds stay the
    min/max over *all* documents: a doc no posting touched holds 0, so
    they are the min/max of the touched docs' sums, with 0 joining them
    whenever fewer than ``n_docs`` docs were touched.  A pool member's
    sums are found by binary search in the row's sorted doc ids; a
    member with no score posting holds 0.
    """
    docs, head, sums = jass.scorer_sums(sdocs, s3, n_docs, cap)
    untouched = jnp.sum(head, axis=1, keepdims=True) < n_docs

    def bound(x):
        lo = jnp.min(jnp.where(head, x, jnp.inf), axis=1, keepdims=True)
        hi = jnp.max(jnp.where(head, x, -jnp.inf), axis=1, keepdims=True)
        return (jnp.where(untouched, jnp.minimum(lo, 0.0), lo),
                jnp.where(untouched, jnp.maximum(hi, 0.0), hi))

    p = jnp.clip(pool, 0)
    pos = jax.vmap(lambda d, v: jnp.searchsorted(
        d, v, method="scan_unrolled"))(docs, p)
    pos = jnp.minimum(pos, docs.shape[1] - 1)
    hit = jnp.take_along_axis(docs, pos, axis=1) == p
    acc = [jnp.where(hit, jnp.take_along_axis(x, pos, axis=1), 0.0)
           for x in sums]
    return second_stage_mix(*acc, tuple(bound(x) for x in sums),
                            doc_len[p], qids, p, seed=seed,
                            noise_weight=noise_weight)


@functools.partial(jax.jit, static_argnames=("depth",))
def rerank_scored(scores: jnp.ndarray, pool: jnp.ndarray,
                  depth: int) -> jnp.ndarray:
    """Rank the docs of ``pool`` (Q, P; -1 padded) by their second-stage
    ``scores`` (Q, P), ties by ascending doc id.

    Returns (Q, depth) doc ids, -1 where the pool runs out.
    """

    def one(sc, p):
        s = jnp.where(p >= 0, sc, -jnp.inf)
        order = jnp.lexsort((p, -s))
        top = order[:depth]
        return jnp.where(s[top] > -jnp.inf, p[top], -1).astype(jnp.int32)

    return jax.vmap(one)(scores, pool)


@functools.partial(jax.jit, static_argnames=("depth",))
def rerank_pool(stage2: jnp.ndarray, pool: jnp.ndarray, depth: int) -> jnp.ndarray:
    """Rank the docs of ``pool`` (Q, P; -1 padded) by the dense
    second-stage scores ``stage2`` (Q, n_docs).

    Returns (Q, depth) doc ids.  Only pool members are eligible — this is
    the restriction semantics used for labeling k.
    """
    scores = jnp.take_along_axis(stage2, jnp.clip(pool, 0), axis=1)
    return rerank_scored(scores, pool, depth)


def gold_run_k(stage2, deep_pool, depth: int) -> jnp.ndarray:
    """A = second stage over the deep pool (paper: depth-10k BM25 pool)."""
    return rerank_pool(stage2, deep_pool, depth)


def candidate_run_k(stage2, deep_pool, k: int, depth: int) -> jnp.ndarray:
    """B_k = second stage over the stage-1 top-k prefix of the pool."""
    prefix = jnp.where(
        jnp.arange(deep_pool.shape[-1])[None, :] < k, deep_pool, -1
    )
    return rerank_pool(stage2, prefix, depth)


def gold_run_rho(doc_stream, impact_stream, n_docs: int, depth: int):
    """Exhaustive score-at-a-time ranking (the exact stage-1 ranking)."""
    return jass.saat_rank(doc_stream, impact_stream, n_docs,
                          doc_stream.shape[-1], depth)


def candidate_run_rho(doc_stream, impact_stream, n_docs: int, rho: int,
                      depth: int):
    """Anytime ranking after processing only the first rho postings."""
    return jass.saat_rank(doc_stream, impact_stream, n_docs, rho, depth)
