"""Safe-to-k candidate generation — the WAND role, TPU-adapted.

WAND is a document-at-a-time heap algorithm whose skipping logic is
pointer-chasing and branch-heavy — a degenerate fit for the MXU.  We keep
its *contract* (an exact, "safe to rank k" top-k of the stage-1 scoring
function) and realize it as dense blocked scoring plus top-k selection
(DESIGN.md section 3): exhaustive quantized accumulation over the query's
postings followed by a two-stage blocked top-k (kernels/topk on TPU).

The k knob keeps its end-to-end meaning: it bounds the candidate pool fed
to feature extraction + reranking, which is where a larger k hurts most in
a multi-stage system.
"""

from __future__ import annotations

import jax.numpy as jnp

from repro.kernels.topk.kernel import KP_MAX
from repro.retrieval import jass

__all__ = ["candidates_topk", "exhaustive_scores", "pool_route",
           "select_pool", "select_pool_from_stream"]


def exhaustive_scores(doc_stream, impact_stream, n_docs: int) -> jnp.ndarray:
    """Dense stage-1 scores: accumulate the entire stream (rho = P)."""
    return jass.saat_scores(doc_stream, impact_stream, n_docs,
                            doc_stream.shape[-1])


def candidates_topk(doc_stream, impact_stream, n_docs: int,
                    k: int) -> jnp.ndarray:
    """Exact top-k candidate pool of the stage-1 scorer.  (Q, k) doc ids,
    -1 padded where fewer than k documents match any query term."""
    scores = exhaustive_scores(doc_stream, impact_stream, n_docs)
    return jass.rank_from_scores(scores, k)


def pool_route(width: int, *, use_kernel: bool) -> str:
    """The path that selects a ``width``-wide pool: ``"pallas"`` (the
    blocked top-k kernel, which holds at most KP_MAX per block) on the
    kernel path, ``"xla"`` (the jnp selection) otherwise — including
    kernel-path pools wider than KP_MAX.  The serving engine decides it
    once per program and reports it (``ServingEngine.topk_routes``)."""
    return "pallas" if use_kernel and width <= KP_MAX else "xla"


def select_pool(scores: jnp.ndarray, depth: int, *, route: str,
                interpret: bool) -> jnp.ndarray:
    """Top-``depth`` doc ids of dense (Q, N) scores, -1 where the score is
    not positive — ``jass.rank_from_scores`` semantics, on the ``route``
    that ``pool_route`` chose: the Pallas blocked top-k kernel
    (``kernels/topk``) or the lexsort of ``rank_from_scores``.

    Both paths break ties toward the lower doc id, so kernel and oracle
    select identical pools.  It reads every one of the N columns: the
    batch-once stage 1 selects here, while the scheduler's finalize, whose
    rows can be positive only at their stream's documents, selects with
    ``select_pool_from_stream``.
    """
    if route == "pallas":
        from repro.kernels.topk import ops as tk_ops
        vals, idxs = tk_ops.topk_select(scores, depth, interpret=interpret)
        return jnp.where(vals > 0, idxs, -1).astype(jnp.int32)
    if route != "xla":
        raise ValueError(f"unknown pool route {route!r}")
    return jass.rank_from_scores(scores, depth)


def select_pool_from_stream(acc: jnp.ndarray, slot_idx: jnp.ndarray,
                            ds_rows: jnp.ndarray, width: int, *,
                            route: str, interpret: bool) -> jnp.ndarray:
    """``select_pool(acc[slot_idx], width, ...)`` bit for bit, read from the
    rows' stream documents alone: (G, width) doc ids, -1 where the score
    is not positive.

    ``acc`` (S, N) holds the slot table's accumulators and ``ds_rows``
    (G, cap) the doc stream of each selected slot (-1 padded); a row must
    be zero outside its stream's documents, as the scheduler's rows are
    (refill zeroes them, chunks add only at stream documents).  Indices
    of ``slot_idx`` past the table clamp to its last row, as in
    ``acc[slot_idx]``, and the caller gathers ``ds_rows`` the same way.

    Each row's stream is sorted by doc id (padding last), a doc listed
    under several terms keeps its first entry, and repeats and padding
    score 0, so only the (G, max(cap, width)) candidates go through
    ``select_pool``.  They sit in ascending doc order, so its ties toward
    the lower position are ties toward the lower doc id, and the
    candidates that are not positive come out -1 as the documents
    outside the stream do in the dense select.
    """
    n_docs = acc.shape[-1]
    docs = jnp.sort(jnp.where(ds_rows >= 0, ds_rows, n_docs), axis=-1)
    first = jnp.concatenate(
        [jnp.ones_like(docs[:, :1], bool), docs[:, 1:] != docs[:, :-1]],
        axis=1) & (docs < n_docs)
    vals = jnp.where(
        first, acc[slot_idx[:, None], jnp.minimum(docs, n_docs - 1)], 0.0)
    pad = width - docs.shape[-1]
    if pad > 0:
        vals = jnp.pad(vals, ((0, 0), (0, pad)))
        docs = jnp.pad(docs, ((0, 0), (0, pad)), constant_values=n_docs)
    pos = select_pool(vals, width, route=route, interpret=interpret)
    return jnp.where(
        pos >= 0, jnp.take_along_axis(docs, jnp.maximum(pos, 0), axis=1),
        -1)
