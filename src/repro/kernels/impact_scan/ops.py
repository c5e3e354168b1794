"""Wrapper for impact_scan with kernel/oracle dispatch and validation.

``rho`` may be a static Python int (the classic JASS call shape — rho==0
short-circuits to zeros without a kernel launch) or a traced (Q,) integer
vector (the serving engine's per-query predicted ρ — one executable
serves every ρ bucket).  Segment bounds (per-posting-block min/max doc
id, see ``retrieval.index.block_doc_bounds``) turn the kernel's dense
(posting-block, doc-block) grid sparse; when absent, full-range bounds
are synthesized and only the ρ skip applies.
"""

from __future__ import annotations

import jax.numpy as jnp
import numpy as np

from repro.kernels.impact_scan.kernel import impact_scan as _kernel
from repro.kernels.impact_scan.kernel import posting_blocks
from repro.kernels.impact_scan.ref import (impact_scan_masked_ref,
                                           impact_scan_ref)

__all__ = ["saat_accumulate", "owned_prefix_len"]


def owned_prefix_len(gpos: jnp.ndarray, rho) -> jnp.ndarray:
    """Shard-local rho for a doc-range-partitioned stream.

    ``gpos`` (Q, cap) is ``partition_postings``' global-stream-position
    column: strictly increasing over each query's kept (owned) prefix,
    with the sentinel P on padding.  The owned postings admitted by a
    global budget ``rho`` therefore form a *prefix* of the local stream,
    and its length — ``count(gpos < rho)`` — is a drop-in rho vector for
    ``saat_accumulate`` on the local stream: the same kernel/oracle path
    serves the partitioned layout with no new masking."""
    rho_vec = jnp.asarray(rho)
    if rho_vec.ndim == 0:
        rho_vec = rho_vec[None]
    return jnp.sum(gpos < rho_vec[:, None], axis=-1).astype(jnp.int32)


def _oracle_stats(rho_vec, seg_bounds, *, qn: int, p: int, n_docs: int,
                  block_p: int, block_d: int) -> jnp.ndarray:
    """Analytic (Q, n_doc_blocks) executed-cell counts for the oracle.

    The oracle runs no grid, but the kernel's live predicate is pure
    arithmetic over (rho, seg bounds), so the counts the kernel *would*
    report are computable exactly — same predicate as
    ``kernel.live_cell_count``, keeping the per-doc-block axis the
    kernel's stats output has instead of collapsing to a scalar."""
    bp, n_p = posting_blocks(p, block_p)
    bd = min(block_d, n_docs)
    n_d = -(-n_docs // bd)
    if seg_bounds is None:
        seg_lo = jnp.zeros((qn, n_p), jnp.int32)
        seg_hi = jnp.full((qn, n_p), n_docs - 1, jnp.int32)
    else:
        seg_lo, seg_hi = seg_bounds
    pb = jnp.arange(n_p, dtype=jnp.int32)
    base = jnp.arange(n_d, dtype=jnp.int32) * bd
    live = ((pb[None, None, :] * bp < rho_vec[:, None, None])
            & (seg_lo[:, None, :] < base[None, :, None] + bd)
            & (seg_hi[:, None, :] >= base[None, :, None]))
    return jnp.sum(live.astype(jnp.int32), axis=2)


def saat_accumulate(doc_stream: jnp.ndarray, impact_stream: jnp.ndarray, *,
                    n_docs: int, rho, use_kernel: bool = True,
                    block_p: int = 512, block_d: int = 2048,
                    seg_bounds=None, with_stats: bool = False,
                    interpret: bool):
    """Score-at-a-time accumulation of the first ``rho`` postings.

    rho: static int or traced (Q,) integer vector.
    seg_bounds: optional (seg_lo, seg_hi) pair, each (Q, n_posting_blocks)
    int32 at the same ``block_p`` (kernel path only).
    with_stats: also return the executed-grid-cell counts — the kernel's
    measured counts on the kernel path, the analytically identical
    predicate sum on the oracle path.
    interpret: no default — the caller states whether the kernel runs
    compiled (TPU) or in the Pallas interpreter.
    """
    qn, p = doc_stream.shape
    static_rho = None
    if isinstance(rho, (int, np.integer)):
        if rho < 0:
            raise ValueError(f"rho must be >= 0, got {rho}")
        static_rho = int(rho)
        rho_vec = jnp.full((qn,), min(rho, p), jnp.int32)
    else:
        rho_vec = jnp.asarray(rho)
        if not jnp.issubdtype(rho_vec.dtype, jnp.integer):
            raise ValueError(
                f"rho_vec must have an integer dtype, got {rho_vec.dtype} "
                "(per-query ρ is a posting count, not a score)")
        if rho_vec.shape != (qn,):
            raise ValueError(f"rho_vec must be shaped ({qn},), got "
                             f"{rho_vec.shape}")
        rho_vec = rho_vec.astype(jnp.int32)

    if not use_kernel:
        if static_rho is not None:
            acc = impact_scan_ref(doc_stream, impact_stream,
                                  n_docs=n_docs, rho=static_rho)
        else:
            acc = impact_scan_masked_ref(doc_stream, impact_stream,
                                         rho_vec, n_docs=n_docs)
        if with_stats:
            # the oracle runs no grid; report the counts the kernel
            # would have, so stats-consuming callers (benchmarks, the
            # scheduler's dispatch accounting) work on either path
            return acc, _oracle_stats(rho_vec, seg_bounds, qn=qn, p=p,
                                      n_docs=n_docs, block_p=block_p,
                                      block_d=block_d)
        return acc

    if static_rho == 0:           # nothing to score: no kernel launch
        zeros = jnp.zeros((qn, n_docs), jnp.float32)
        if with_stats:
            bd = min(block_d, n_docs)
            return zeros, jnp.zeros((qn, -(-n_docs // bd)), jnp.int32)
        return zeros

    if seg_bounds is None:        # full-range bounds: only the ρ skip fires
        _, n_p = posting_blocks(p, block_p)
        seg_lo = jnp.zeros((qn, n_p), jnp.int32)
        seg_hi = jnp.full((qn, n_p), n_docs - 1, jnp.int32)
    else:
        seg_lo, seg_hi = seg_bounds
    return _kernel(doc_stream, impact_stream, rho_vec, seg_lo, seg_hi,
                   n_docs=n_docs, block_p=block_p, block_d=block_d,
                   with_stats=with_stats, interpret=interpret)
