"""Score-at-a-time anytime evaluation (JASS; Lin & Trotman 2015).

JASS traverses impact-ordered posting segments in decreasing impact order,
accumulating quantized integer impacts per document, and can stop any time;
the knob rho = number of postings processed.  TPU adaptation (DESIGN.md
section 3): the impact-ordered traversal becomes

  1. ``gather_streams``  — gather the top-impact prefix of each query
     term's postings and merge them into one impact-descending stream per
     query (a vectorized sort replaces the CPU segment heap),
  2. ``saat_scores``     — accumulate the first rho stream entries into a
     dense document accumulator (the Pallas ``impact_scan`` kernel is the
     production path; the jnp path here is its oracle and the CPU default),
  3. ``rank_from_scores`` — deterministic ranking (ties by doc id).

Early termination is a mask on the jnp oracle paths and a *run-time grid
skip* on the kernel path: ``saat_scores_masked`` hands the traced
per-query rho vector to ``impact_scan`` (scalar prefetch), whose grid
cells at and beyond rho never execute — preserving the paper's linear
rho <-> work relationship per query inside one batched dispatch.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.topk.ref import top_k_lowest_index

__all__ = ["gather_streams", "saat_scores", "saat_scores_masked",
           "rank_from_scores", "saat_rank"]


@functools.partial(jax.jit, static_argnames=("cap",))
def gather_streams(offsets: jnp.ndarray, postings_doc: jnp.ndarray,
                   postings_impact: jnp.ndarray, query_terms: jnp.ndarray,
                   cap: int):
    """Build per-query impact-descending posting streams.

    offsets: (V+1,) int64 CSR offsets (impact-ordered within term).
    query_terms: (Q, L) int32, -1 padded.
    cap: stream length P (= max rho of interest).

    Returns (doc_stream, impact_stream): (Q, P) int32 / float32, padded with
    doc -1 / impact -1 where the stream is exhausted.
    """
    nnz = postings_doc.shape[0]
    q = jnp.clip(query_terms, 0)
    start = offsets[q]                                  # (Q, L)
    end = offsets[jnp.clip(query_terms + 1, 0)]
    end = jnp.where(query_terms >= 0, end, start)
    ar = jnp.arange(cap, dtype=start.dtype)             # (P,)
    idx = start[..., None] + ar                         # (Q, L, P)
    valid = idx < end[..., None]
    idx = jnp.clip(idx, 0, nnz - 1)
    docs = jnp.where(valid, postings_doc[idx], -1)
    imps = jnp.where(valid, postings_impact[idx].astype(jnp.float32), -1.0)
    qn, ln = query_terms.shape
    docs = docs.reshape(qn, ln * cap)
    imps = imps.reshape(qn, ln * cap)
    # impact-descending; equal impacts keep their gathered (term, doc)
    # order, on every backend
    top_imps, top_idx = top_k_lowest_index(imps, cap)
    top_docs = jnp.take_along_axis(docs, top_idx, axis=1)
    return top_docs.astype(jnp.int32), top_imps


def saat_scores(doc_stream: jnp.ndarray, impact_stream: jnp.ndarray,
                n_docs: int, rho: int | jnp.ndarray) -> jnp.ndarray:
    """Accumulate the first ``rho`` postings of each stream.  (Q, n_docs)."""

    def one(docs, imps):
        mask = (jnp.arange(docs.shape[0]) < rho) & (docs >= 0)
        contrib = jnp.where(mask, imps, 0.0)
        return jnp.zeros(n_docs, jnp.float32).at[jnp.clip(docs, 0)].add(contrib)

    return jax.vmap(one)(doc_stream, impact_stream)


def saat_scores_masked(doc_stream: jnp.ndarray, impact_stream: jnp.ndarray,
                       rho_vec: jnp.ndarray, n_docs: int, *,
                       use_kernel: bool = False, interpret: bool,
                       seg_bounds=None, block_p: int = 512,
                       block_d: int = 2048) -> jnp.ndarray:
    """Accumulate the first ``rho_vec[q]`` postings of each query's stream.

    The single-dispatch serving engine's form of ``saat_scores``: rho is a
    *traced* (Q,) vector, so one executable serves every rho bucket — the
    per-query truncation becomes run-time masking instead of a static
    stream length.  With a constant rho_vec this computes bit-identical
    accumulators to ``saat_scores`` (same mask, same scatter-add).

    ``use_kernel`` routes the accumulation through the Pallas
    ``impact_scan`` kernel with ρ as a *traced scalar-prefetch operand*:
    the kernel skips posting blocks at and beyond each query's ρ at run
    time (plus, with ``seg_bounds`` — per-posting-block min/max doc id
    from ``index.block_doc_bounds`` at the same ``block_p`` — every
    (posting, doc)-block cell whose id range misses the doc tile), so
    cheap queries actually stop early instead of paying a pre-masked
    full-stream scan.  ``interpret`` has no default: the caller states
    whether that kernel runs compiled or interpreted.
    """
    if use_kernel:
        from repro.kernels.impact_scan import ops as is_ops
        return is_ops.saat_accumulate(
            doc_stream, impact_stream, n_docs=n_docs,
            rho=jnp.asarray(rho_vec), seg_bounds=seg_bounds,
            block_p=block_p, block_d=block_d, interpret=interpret)
    p = doc_stream.shape[-1]
    mask = ((jnp.arange(p)[None, :] < rho_vec[:, None])
            & (doc_stream >= 0))
    contrib = jnp.where(mask, impact_stream, 0.0)

    def one(docs, c):
        return jnp.zeros(n_docs, jnp.float32).at[jnp.clip(docs, 0)].add(c)

    return jax.vmap(one)(doc_stream, contrib)


@functools.partial(jax.jit, static_argnames=("depth",))
def rank_from_scores(scores: jnp.ndarray, depth: int) -> jnp.ndarray:
    """Top-``depth`` doc ids, ties broken by ascending doc id; zero-score
    docs are excluded (padded with -1)."""
    vals, top = top_k_lowest_index(scores, min(depth, scores.shape[-1]))
    return jnp.where(vals > 0, top, -1).astype(jnp.int32)


def saat_rank(doc_stream, impact_stream, n_docs: int, rho: int,
              depth: int) -> jnp.ndarray:
    """Convenience: anytime ranking at rho, evaluated to ``depth``."""
    return rank_from_scores(
        saat_scores(doc_stream, impact_stream, n_docs, rho), depth
    )


@functools.partial(jax.jit, static_argnames=("cap",))
def gather_score_streams(offsets: jnp.ndarray, postings_doc: jnp.ndarray,
                         postings_score: jnp.ndarray,
                         query_terms: jnp.ndarray, cap: int):
    """Gather each query's postings with their (bm25, lm, tfidf) scores —
    the stage-2 feature-extraction read.  Unsorted (exhaustive use only).

    Returns (docs (Q, L*cap) int32 -1-padded, scores (Q, L*cap, 3))."""
    nnz = postings_doc.shape[0]
    q = jnp.clip(query_terms, 0)
    start = offsets[q]
    end = offsets[jnp.clip(query_terms + 1, 0)]
    end = jnp.where(query_terms >= 0, end, start)
    ar = jnp.arange(cap, dtype=start.dtype)
    idx = start[..., None] + ar
    valid = idx < end[..., None]
    idx = jnp.clip(idx, 0, nnz - 1)
    docs = jnp.where(valid, postings_doc[idx], -1)
    scores = jnp.where(valid[..., None], postings_score[idx], 0.0)
    qn, ln = query_terms.shape
    return docs.reshape(qn, ln * cap), scores.reshape(qn, ln * cap, 3)


def scorer_accumulators(docs: jnp.ndarray, scores3: jnp.ndarray,
                        n_docs: int):
    """Dense per-scorer accumulators: (Q, n_docs) x3 from gathered
    postings.  These are the stage-2 features of the reranker stand-in.

    The oracle form, for paths that score every doc (labelling, the
    per-bucket reference, the sharded engine); the single-chip serving
    programs use ``scorer_sums``, which gives the same sums for touched
    docs without the (Q, n_docs, 3) block."""

    def one(d, s):
        safe = jnp.clip(d, 0)
        w = (d >= 0)[:, None]
        z = jnp.zeros((n_docs, 3), jnp.float32)
        return z.at[safe].add(jnp.where(w, s, 0.0))

    acc = jax.vmap(one)(docs, scores3)       # (Q, n_docs, 3)
    return acc[..., 0], acc[..., 1], acc[..., 2]


def scorer_sums(docs: jnp.ndarray, scores3: jnp.ndarray, n_docs: int,
                cap: int):
    """Per-doc sums of gathered score postings, one per distinct doc: the
    sparse form of ``scorer_accumulators``, with no ``n_docs``-wide array.

    ``docs`` (Q, L*cap) holds query term ``t``'s postings in columns
    ``[t*cap, (t+1)*cap)``, -1 padded (``gather_score_streams``); a doc
    appears at most once per term.  Each row is sorted by the unique key
    ``doc*L + t`` (padding last), so a doc's entries lie side by side in
    term order, and ``L - 1`` shifted masked adds sum them at the first
    of them as ``((0 + v_0) + v_1) + ...``: the order in which the
    scatter-add of ``scorer_accumulators`` meets them, so the sums are
    bit-identical.

    Returns (sdocs (Q, L*cap) int32 ascending, ``n_docs`` on padding;
    head (Q, L*cap) bool, true at each doc's first entry; sums, one
    (Q, L*cap) float32 per scorer, each doc's sums at its head).  The
    scores travel through the sort as payloads, one row per scorer: a
    gather of (Q, L*cap, 3) rows would pad the 3 to a full lane tile on
    a TPU.
    """
    q, n = docs.shape
    n_terms = n // cap
    if n_docs * n_terms >= 2**31:
        raise ValueError(
            f"n_docs * query terms = {n_docs} * {n_terms} does not fit "
            "the int32 sort key of scorer_sums")
    term = jnp.arange(n, dtype=jnp.int32) // cap
    key = jnp.where(docs >= 0, docs * n_terms + term[None, :],
                    n_docs * n_terms)
    key, *vals = jax.lax.sort(
        (key,) + tuple(scores3[..., k] for k in range(scores3.shape[-1])),
        dimension=1, is_stable=False, num_keys=1)
    sdocs = key // n_terms
    # one step per later term: entry i takes entry i+k's value while
    # both hold the same doc (a run is at most n_terms long)
    fill = ((0, 0), (0, n_terms - 1))
    dpad = jnp.pad(sdocs, fill, constant_values=-1)
    vpad = [jnp.pad(v, fill) for v in vals]
    sums = list(vals)
    for k in range(1, n_terms):
        same = dpad[:, k:k + n] == sdocs
        sums = [jnp.where(same, s + vp[:, k:k + n], s)
                for s, vp in zip(sums, vpad)]
    first = jnp.concatenate(
        [jnp.ones((q, 1), bool), sdocs[:, 1:] != sdocs[:, :-1]], axis=1)
    return sdocs, first & (sdocs < n_docs), tuple(sums)
