"""Inverted index build: postings, per-term score statistics, impacts.

This is the indexer of the candidate-generation stage.  It produces:

  * term-major CSR postings (offsets / doc ids / term frequencies),
  * per-posting similarity scores under the paper's three scorers,
  * the per-term score statistics of Table 1 (max, quartiles, min, means,
    median, variance, IQR) for each scorer — precomputed at index time and
    "stored with the postings list" exactly as the paper prescribes,
  * 8-bit quantized impact scores and an impact-descending posting order
    (the JASS impact-ordered layout used by score-at-a-time evaluation).

The build is host-side numpy (this is the offline indexer); query-time
consumers gather from the arrays with jnp.  ``block_doc_bounds`` is the
index's segment-metadata producer for the Pallas ``impact_scan`` kernel:
per-posting-block min/max doc id, computed wherever an impact-ordered
stream is materialized (the per-query streams are merges of the
impact-ordered lists built here, so the metadata is defined on the
merged stream, at the kernel's posting-block granularity).
"""

from __future__ import annotations

import math
from concurrent.futures import ThreadPoolExecutor
from dataclasses import dataclass

import jax
import jax.numpy as jnp
import numpy as np

from repro.retrieval import scoring
from repro.retrieval.corpus import Corpus

__all__ = ["InvertedIndex", "TermStats", "build_index", "block_doc_bounds",
           "partition_cap", "partition_postings",
           "partition_scored_postings", "STAT_NAMES"]

#: order of the 9 per-term score statistics (Table 1, items 3-11)
STAT_NAMES = ("max", "q1", "q3", "min", "amean", "hmean", "median", "var", "iqr")


@dataclass
class TermStats:
    """Per-term statistics, precomputed at index time.

    stats: (vocab, n_scorers, 9) float32 in STAT_NAMES order.
    ctf:   (vocab,) collection term frequency C_t.
    df:    (vocab,) document frequency f_t.
    """

    stats: np.ndarray
    ctf: np.ndarray
    df: np.ndarray


@dataclass
class InvertedIndex:
    corpus: Corpus
    collection: scoring.CollectionStats
    offsets: np.ndarray       # (vocab+1,) int64 CSR offsets, impact-ordered
    postings_doc: np.ndarray  # (nnz,) int32 doc ids, impact-desc within term
    postings_tf: np.ndarray   # (nnz,) int32
    postings_score: np.ndarray   # (nnz, n_scorers) float32 (bm25, lm, tfidf)
    postings_impact: np.ndarray  # (nnz,) uint8 quantized bm25 impact
    impact_scale: tuple[float, float]  # (lo, hi) of the quantizer
    term_stats: TermStats

    @property
    def vocab(self) -> int:
        return self.offsets.shape[0] - 1

    @property
    def nnz(self) -> int:
        return self.postings_doc.shape[0]

    def postings_of(self, term: int) -> slice:
        return slice(int(self.offsets[term]), int(self.offsets[term + 1]))


def block_doc_bounds(doc_stream: jnp.ndarray, *, block_p: int,
                     n_docs: int) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Per-posting-block min/max doc id — the impact_scan segment skips.

    doc_stream: (Q, P) int32 impact-ordered doc ids, -1 padded.  Blocks
    follow the kernel's grid exactly (``posting_blocks``: ``block_p``
    clamped to the stream length), so the returned (Q, n_p) int32 arrays
    feed ``saat_accumulate(seg_bounds=...)`` unchanged.  A (posting,
    doc)-block grid cell runs only when [lo, hi] intersects the doc tile;
    blocks that are pure padding (exhausted streams — every posting
    beyond any useful ρ) carry the empty interval ``(n_docs, -1)`` and
    are never executed.
    """
    from repro.kernels.impact_scan.kernel import posting_blocks

    qn, p = doc_stream.shape
    bp, n_p = posting_blocks(p, block_p)
    d = doc_stream
    if n_p * bp != p:
        d = jnp.pad(d, ((0, 0), (0, n_p * bp - p)), constant_values=-1)
    d = d.reshape(qn, n_p, bp)
    lo = jnp.min(jnp.where(d >= 0, d, n_docs), axis=-1)
    hi = jnp.max(d, axis=-1)            # padding is -1: empty block -> -1
    return lo.astype(jnp.int32), hi.astype(jnp.int32)


def partition_cap(cap: int, n_shards: int, slack: float,
                  multiple: int = 8) -> int:
    """Per-shard stream length for a doc-range partition of a ``cap``-long
    stream over ``n_shards`` shards.

    A uniformly-random doc assignment puts ~cap/n_shards postings on each
    shard; ``slack`` (>= 1) is the headroom multiplier for skew (doc ids
    are *not* uniform in an impact-ordered stream).  The result is aligned
    up to ``multiple`` and never exceeds ``cap`` (one shard degenerates to
    the identity partition).  Overflow past this cap is detected at run
    time by ``partition_postings`` and surfaced by the engine.
    """
    if n_shards <= 1:
        return cap
    raw = int(math.ceil(slack * cap / n_shards))
    raw = -(-max(raw, 1) // multiple) * multiple
    return min(cap, raw)


def partition_postings(doc_stream: jnp.ndarray, impact_stream: jnp.ndarray,
                       lo, *, width: int, cap: int):
    """Doc-range partition of impact-ordered streams (shard_map body).

    Compacts each query's postings whose doc id falls in
    ``[lo, lo + width)`` into the leading columns of a ``cap``-wide
    shard-local stream, *preserving global stream order*: the j-th local
    column takes the j-th owned posting, found by binary search over the
    running owned count (``searchsorted(cumsum(own), j+1)``) — O(cap
    log P) with no sort or scatter, which XLA:CPU executes an order of
    magnitude faster than an argsort compaction of the same stream.

    Returns
      ds_loc: (Q, cap) int32 shard-LOCAL doc ids (``doc - lo``), -1 padded
      im_loc: (Q, cap) float32 impacts, -1 padded
      gpos:   (Q, cap) int32 global stream position of each kept posting
              (P for padding) — strictly increasing over the kept prefix,
              so ``count(gpos < rho)`` is the shard-local rho prefix
      overflow: (Q,) int32 owned postings dropped for exceeding ``cap``
                (0 everywhere when the slack held)
    """
    qn, p = doc_stream.shape
    own = (doc_stream >= lo) & (doc_stream < lo + width)
    csum = jnp.cumsum(own, axis=-1, dtype=jnp.int32)
    j = jnp.arange(cap, dtype=jnp.int32) + 1
    src = jax.vmap(lambda c: jnp.searchsorted(c, j, side="left"))(csum)
    valid = j[None, :] <= csum[:, -1:]
    src_c = jnp.minimum(src, p - 1)
    ds_loc = jnp.where(
        valid, jnp.take_along_axis(doc_stream, src_c, axis=1) - lo,
        -1).astype(jnp.int32)
    im_loc = jnp.where(
        valid, jnp.take_along_axis(impact_stream, src_c, axis=1), -1.0)
    gpos = jnp.where(valid, src, p).astype(jnp.int32)
    overflow = jnp.maximum(csum[:, -1] - cap, 0).astype(jnp.int32)
    return ds_loc, im_loc, gpos, overflow


def partition_scored_postings(sdocs: jnp.ndarray, s3: jnp.ndarray,
                              lo, *, width: int, cap: int):
    """Doc-range partition of the stage-2 score streams (shard_map body).

    Same order-preserving searchsorted compaction as
    ``partition_postings`` without the global-position bookkeeping
    (stage 2 is exhaustive — no rho prefix).

    Returns (sd_loc (Q, cap) int32 local ids -1 padded,
             s3_loc (Q, cap, 3) float32 zero padded,
             overflow (Q,) int32).
    """
    qn, p = sdocs.shape
    own = (sdocs >= lo) & (sdocs < lo + width)
    csum = jnp.cumsum(own, axis=-1, dtype=jnp.int32)
    j = jnp.arange(cap, dtype=jnp.int32) + 1
    src = jax.vmap(lambda c: jnp.searchsorted(c, j, side="left"))(csum)
    valid = j[None, :] <= csum[:, -1:]
    src_c = jnp.minimum(src, p - 1)
    sd_loc = jnp.where(
        valid, jnp.take_along_axis(sdocs, src_c, axis=1) - lo,
        -1).astype(jnp.int32)
    s3_loc = jnp.where(
        valid[..., None],
        jnp.take_along_axis(s3, src_c[..., None], axis=1), 0.0)
    overflow = jnp.maximum(csum[:, -1] - cap, 0).astype(jnp.int32)
    return sd_loc, s3_loc, overflow


def _segment_quantiles(sorted_vals: np.ndarray, offsets: np.ndarray,
                       q: float) -> np.ndarray:
    """Per-segment quantile over values sorted ascending within segments."""
    lens = np.diff(offsets)
    idx = offsets[:-1] + np.floor(q * np.maximum(lens - 1, 0)).astype(np.int64)
    idx = np.minimum(idx, np.maximum(offsets[1:] - 1, 0))
    out = sorted_vals[np.minimum(idx, len(sorted_vals) - 1)] if len(sorted_vals) else np.zeros_like(lens, dtype=np.float32)
    return np.where(lens > 0, out, 0.0).astype(np.float32)


def _sort_by_term(scores: np.ndarray, term_of: np.ndarray):
    """(scores, terms) sorted by (term, score) ascending.

    One direct sort of packed uint64 keys — term in the high word, the
    float32 score mapped to an order-preserving uint32 in the low word —
    instead of an indirect two-key lexsort, which is over ten times
    slower at a deployment's postings count."""
    bits = scores.astype(np.float32).view(np.uint32)
    neg = (bits >> 31).astype(bool)
    low = np.where(neg, ~bits, bits | np.uint32(0x80000000))
    key = (term_of.astype(np.uint64) << np.uint64(32)) | low
    key.sort()
    low = (key & np.uint64(0xFFFFFFFF)).astype(np.uint32)
    pos = (low >> 31).astype(bool)
    bits = np.where(pos, low & np.uint32(0x7FFFFFFF), ~low)
    return bits.view(np.float32), (key >> np.uint64(32)).astype(np.int64)


def _term_statistics(scores: np.ndarray, term_of: np.ndarray,
                     vocab: int) -> np.ndarray:
    """9 stats per term for one scorer's posting scores. O(nnz log nnz)."""
    s, t = _sort_by_term(scores, term_of)
    s = s.astype(np.float64)
    counts = np.bincount(t, minlength=vocab).astype(np.int64)
    offsets = np.concatenate([[0], np.cumsum(counts)])
    lens = np.maximum(counts, 1)

    sums = np.bincount(t, weights=s, minlength=vocab)
    sq = np.bincount(t, weights=s * s, minlength=vocab)
    amean = sums / lens
    var = np.maximum(sq / lens - amean**2, 0.0)
    # harmonic mean needs positive values; shift into positive range the same
    # way for every term (LM scores are negative log-probs): hmean over
    # (s - global_min + 1)
    shift = 1.0 - s.min() if len(s) else 1.0
    inv = np.bincount(t, weights=1.0 / (s + shift), minlength=vocab)
    hmean = lens / np.maximum(inv, 1e-12) - shift

    smax = _segment_quantiles(s, offsets, 1.0)
    smin = _segment_quantiles(s, offsets, 0.0)
    q1 = _segment_quantiles(s, offsets, 0.25)
    q3 = _segment_quantiles(s, offsets, 0.75)
    med = _segment_quantiles(s, offsets, 0.5)

    out = np.stack(
        [smax, q1, q3, smin, amean, hmean, med, var, q3 - q1], axis=-1
    ).astype(np.float32)
    out[counts == 0] = 0.0
    return out


def _impact_order(term_of: np.ndarray, impact: np.ndarray,
                  doc_ids: np.ndarray, levels: int,
                  vocab: int) -> np.ndarray:
    """Posting order of the impact-ordered layout: (term, -impact, doc).

    The (term, doc) pairs are unique, so one packed-key argsort gives the
    three-key lexsort's order whenever the key fits in 64 bits."""
    if vocab * (levels + 1) < 1 << 31:
        key = (((term_of.astype(np.uint64) * np.uint64(levels + 1)
                 + (levels - impact).astype(np.uint64)) << np.uint64(32))
               | doc_ids.astype(np.uint32).astype(np.uint64))
        return np.argsort(key)
    return np.lexsort((doc_ids, -impact.astype(np.int32), term_of))


def build_index(corpus: Corpus, impact_bits: int = 8) -> InvertedIndex:
    vocab = corpus.config.vocab
    col = scoring.CollectionStats(
        n_docs=corpus.n_docs,
        total_terms=corpus.total_terms,
        avg_doc_len=float(corpus.doc_len.mean()),
    )
    term_of = corpus.term_ids.astype(np.int64)
    tf = corpus.counts.astype(np.float64)
    dlen = corpus.doc_len[corpus.doc_ids].astype(np.float64)
    df_all = np.bincount(term_of, minlength=vocab).astype(np.float64)
    ctf_all = np.bincount(term_of, weights=tf, minlength=vocab)
    df = df_all[term_of]
    ctf = ctf_all[term_of]

    s_bm25 = np.asarray(scoring.bm25(tf, df, dlen, col), dtype=np.float32)
    s_lm = np.asarray(scoring.dirichlet_lm(tf, ctf, dlen, col), dtype=np.float32)
    s_tfidf = np.asarray(scoring.tfidf(tf, df, dlen, col), dtype=np.float32)
    scores = np.stack([s_bm25, s_lm, s_tfidf], axis=-1)

    # impact quantization (JASS): global linear quantizer over bm25 scores
    lo, hi = float(s_bm25.min()), float(s_bm25.max())
    levels = (1 << impact_bits) - 1
    impact = np.round((s_bm25 - lo) / max(hi - lo, 1e-9) * levels)
    impact = impact.astype(np.uint8 if impact_bits <= 8 else np.uint16)

    # four independent sorts — the Table 1 statistics of each scorer and
    # the impact-ordered layout — run side by side (numpy drops the GIL
    # in them)
    with ThreadPoolExecutor(4) as pool:
        per_scorer = [pool.submit(_term_statistics, scores[:, i], term_of,
                                  vocab) for i in range(3)]
        order = pool.submit(_impact_order, term_of, impact,
                            corpus.doc_ids, levels, vocab).result()
        stats = np.stack([f.result() for f in per_scorer],
                         axis=1)  # (vocab, 3, 9)
    counts = np.bincount(term_of, minlength=vocab).astype(np.int64)
    offsets = np.concatenate([[0], np.cumsum(counts)])

    return InvertedIndex(
        corpus=corpus,
        collection=col,
        offsets=offsets,
        postings_doc=corpus.doc_ids[order],
        postings_tf=corpus.counts[order],
        postings_score=scores[order],
        postings_impact=impact[order],
        impact_scale=(lo, hi),
        term_stats=TermStats(stats=stats, ctf=ctf_all.astype(np.float32),
                             df=df_all.astype(np.float32)),
    )
