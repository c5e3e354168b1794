"""Pallas TPU kernel: JASS score-at-a-time impact accumulation.

The ρ knob's inner loop: add quantized impact contributions of the first
``rho[q]`` postings of a query's impact-ordered stream into a dense
document accumulator.  On CPU JASS this is a scalar scatter loop; the TPU
adaptation (DESIGN.md §3) reformulates the scatter as a *blocked one-hot
matmul*, which the MXU executes densely:

    grid = (Q / ROWS, n_doc_blocks, n_posting_blocks)
    acc[q, db] += impacts[q, pb] @ onehot(doc_ids[q, pb] == doc_range(db))
                  for each of the cell's ROWS = 8 queries q

Every block is (8, block) over plain 2-D (Q, ·) arrays: the chip's
compiler needs a block's last two dimensions to be multiples of
(8, 128) or whole, so a cell takes eight query rows, not one.

ρ is a **traced per-query scalar**, delivered to the kernel through
scalar prefetch (SMEM), so one compiled executable serves every ρ bucket
— the grid stays the full padded stream length and early termination
happens per query row of each grid cell at run time:

  * ``pl.when(pb * block_p < rho[q])`` skips posting blocks entirely
    beyond the query's ρ — the anytime knob as a run-time skip,
  * a within-block mask kills the ragged tail where ρ cuts mid-block.

Segment metadata makes the dense grid sparse in the doc dimension too:
``seg_lo``/``seg_hi`` carry each posting block's min/max doc id (computed
where the stream is materialized — ``retrieval.index.block_doc_bounds``),
and a (posting-block, doc-block) cell is skipped when the block's doc-id
range does not intersect the doc tile.  Exhausted stream blocks carry the
empty interval ``(n_docs, -1)`` and never execute.

With a constant ρ vector the output is bit-identical to
``impact_scan_ref`` for integer-valued impacts (the production streams
are 8-bit quantized, so every partial sum is exact in f32; see
tests/test_kernels.py).

VMEM at defaults (block_p=512, block_d=2048): onehot tile 512*2048*4B =
4 MiB + acc tile 8*2048*4B = 64 KiB — fits the 16 MiB v5e VMEM.  The
scalar-prefetch operands (ρ and the segment bounds, flattened to 1-D)
are tiny int32 arrays resident in SMEM before the body runs, which is
what lets the skip predicates gate the DMA-fed compute without touching
VMEM.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["ROWS", "impact_scan", "live_cell_count", "posting_blocks"]

#: query rows per grid cell — the sublane count of a (8, 128) VMEM tile,
#: so every block's second-minor dimension is one whole tile
ROWS = 8


def posting_blocks(p: int, block_p: int) -> tuple[int, int]:
    """(clamped block size, block count) for a stream of length ``p``.

    Shared by the kernel and every producer of per-block segment metadata
    so bounds arrays always agree with the kernel's grid.
    """
    bp = min(block_p, p)
    return bp, -(-p // bp)


def _impact_kernel(rho_ref, seg_lo_ref, seg_hi_ref, docs_ref, imps_ref,
                   acc_ref, *stats_ref, block_p: int, block_d: int,
                   n_p: int, n_d: int, with_stats: bool):
    g = pl.program_id(0)
    db = pl.program_id(1)
    pb = pl.program_id(2)

    @pl.when(pb == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    if with_stats:
        @pl.when((pb == 0) & (db == 0))
        def _init_stats():
            stats_ref[0][...] = jnp.zeros_like(stats_ref[0])

    base = db * block_d
    pidx = pb * block_p + jax.lax.broadcasted_iota(jnp.int32,
                                                   (1, block_p), 1)
    # ROWS queries share a grid cell; each row keeps its own run-time
    # skip (ρ early termination + segment intersection)
    for r in range(ROWS):
        q = g * ROWS + r
        rho = rho_ref[q]
        seg = q * n_p + pb
        live = ((pb * block_p < rho)
                & (seg_lo_ref[seg] < base + block_d)
                & (seg_hi_ref[seg] >= base))

        @pl.when(live)
        def _body():
            docs = docs_ref[r:r + 1, :]                  # (1, block_p)
            # rho mask: global posting index < rho[q]; padding (-1) dropped
            keep = (pidx < rho) & (docs >= 0)
            w = jnp.where(keep, imps_ref[r:r + 1, :], 0.0)
            # transposed one-hot over this doc tile: (block_d, block_p),
            # docs broadcast along sublanes (no lane->sublane relayout)
            onehot = (jax.lax.broadcasted_iota(jnp.int32,
                                               (block_d, block_p), 0)
                      == docs - base)
            contrib = jax.lax.dot_general(
                w, onehot.astype(jnp.float32), (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)      # (1, block_d)
            acc_ref[r:r + 1, :] += contrib
            if with_stats:
                hit = jax.lax.broadcasted_iota(jnp.int32, (1, n_d), 1) == db
                stats_ref[0][r:r + 1, :] += hit.astype(jnp.int32)


@functools.partial(
    jax.jit, static_argnames=("n_docs", "block_p", "block_d", "with_stats",
                              "interpret"))
def impact_scan(doc_stream: jnp.ndarray, impact_stream: jnp.ndarray,
                rho_vec: jnp.ndarray, seg_lo: jnp.ndarray,
                seg_hi: jnp.ndarray, *, n_docs: int, block_p: int = 512,
                block_d: int = 2048, with_stats: bool = False,
                interpret: bool):
    """Accumulate the first ``rho_vec[q]`` postings of each stream.

    doc_stream: (Q, P) int32 (-1 padded), impact_stream: (Q, P) f32, both
    impact-descending.  rho_vec: (Q,) int32 traced per-query ρ.
    seg_lo/seg_hi: (Q, n_posting_blocks) int32 per-block min/max doc id
    (empty blocks: the empty interval ``(n_docs, -1)``).  ``interpret``
    has no default: the caller states whether the body runs compiled
    (TPU) or in the Pallas interpreter.

    Returns (Q, n_docs) accumulators equal to processing exactly the
    first ``rho_vec[q]`` postings of query ``q``; with ``with_stats``
    also returns a (Q, n_doc_blocks) int32 count of grid-cell bodies
    actually executed (the dense kernel would run
    ``n_doc_blocks * n_posting_blocks`` per query).
    """
    qn, p = doc_stream.shape
    bp, n_p = posting_blocks(p, block_p)
    if rho_vec.shape != (qn,):
        raise ValueError(f"rho_vec must be shaped ({qn},), got "
                         f"{rho_vec.shape}")
    if seg_lo.shape != (qn, n_p) or seg_hi.shape != (qn, n_p):
        raise ValueError(
            f"segment bounds must be shaped ({qn}, {n_p}) for block_p="
            f"{block_p} (got {seg_lo.shape} / {seg_hi.shape}); compute "
            "them with retrieval.index.block_doc_bounds at the same "
            "block size")
    bd = min(block_d, n_docs)
    n_d = -(-n_docs // bd)
    # pad the ragged stream tail so the last block reads real data, and
    # the query axis to whole ROWS groups (padding rows carry rho 0 and
    # the empty segment, so they never execute)
    q_pad = -(-qn // ROWS) * ROWS
    p_pad = n_p * bp
    rows, cols = (0, q_pad - qn), (0, p_pad - p)
    doc_stream = jnp.pad(doc_stream, (rows, cols), constant_values=-1)
    impact_stream = jnp.pad(impact_stream, (rows, cols),
                            constant_values=0.0)
    rho_vec = jnp.pad(rho_vec.astype(jnp.int32), rows)
    # 1-D scalar-prefetch operands: row q's bounds start at q * n_p
    seg_lo = jnp.pad(seg_lo.astype(jnp.int32), (rows, (0, 0)),
                     constant_values=n_docs).reshape(-1)
    seg_hi = jnp.pad(seg_hi.astype(jnp.int32), (rows, (0, 0)),
                     constant_values=-1).reshape(-1)

    kernel = functools.partial(_impact_kernel, block_p=bp, block_d=bd,
                               n_p=n_p, n_d=n_d, with_stats=with_stats)
    out_specs = [pl.BlockSpec((ROWS, bd), lambda g, d, s, *refs: (g, d))]
    out_shape = [jax.ShapeDtypeStruct((q_pad, n_d * bd), jnp.float32)]
    if with_stats:
        out_specs.append(
            pl.BlockSpec((ROWS, n_d), lambda g, d, s, *refs: (g, 0)))
        out_shape.append(jax.ShapeDtypeStruct((q_pad, n_d), jnp.int32))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,           # rho_vec, seg_lo, seg_hi in SMEM
        grid=(q_pad // ROWS, n_d, n_p),
        in_specs=[
            pl.BlockSpec((ROWS, bp), lambda g, d, s, *refs: (g, s)),
            pl.BlockSpec((ROWS, bp), lambda g, d, s, *refs: (g, s)),
        ],
        out_specs=out_specs,
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=out_shape,
        interpret=interpret,
    )(rho_vec, seg_lo, seg_hi, doc_stream, impact_stream)
    acc = out[0][:qn, :n_docs]
    return (acc, out[1][:qn]) if with_stats else acc


def live_cell_count(rho_vec, seg_lo, seg_hi, *, p: int, n_docs: int,
                    block_p: int = 512, block_d: int = 2048) -> jnp.ndarray:
    """Grid-cell bodies the kernel will execute — the same predicate the
    kernel evaluates, summed over the grid.  The dense kernel executes
    ``Q * n_doc_blocks * n_posting_blocks``; benchmarks report both."""
    bp, n_p = posting_blocks(p, block_p)
    bd = min(block_d, n_docs)
    n_d = -(-n_docs // bd)
    pb = jnp.arange(n_p, dtype=jnp.int32)
    base = jnp.arange(n_d, dtype=jnp.int32) * bd
    live = ((pb[None, None, :] * bp < rho_vec[:, None, None])
            & (seg_lo[:, None, :] < base[None, :, None] + bd)
            & (seg_hi[:, None, :] >= base[None, :, None]))
    return jnp.sum(live.astype(jnp.int32))
