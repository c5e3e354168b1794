"""Pallas TPU kernel: EmbeddingBag (gather + bag reduce) via scalar prefetch.

The recsys lookup hot path (serve_bulk scores 262k requests x 40 fields).
JAX has no EmbeddingBag; the TPU-native pattern is *scalar-prefetched
dynamic block indexing*: bag indices ride in SMEM ahead of the grid, and
the table's BlockSpec index_map selects the (1, D) table row block for
each (batch, slot) grid step — Mosaic double-buffers the HBM row fetches.

    grid = (B, L); table block (1, D) chosen by ids[b, l]; output block
    (1, D) accumulates in VMEM; padding ids (-1) contribute zero via
    pl.when; combiner "mean" divides on the last slot.

VMEM: one table row + one output row (D <= 128 floats) — trivially
resident; the win is the prefetch pipeline, not tiling.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["embedding_bag_kernel"]


def _bag_kernel(ids_ref, counts_ref, table_ref, out_ref, *, n_slots: int,
                mean: bool):
    b = pl.program_id(0)
    l = pl.program_id(1)

    @pl.when(l == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    @pl.when(ids_ref[b, l] >= 0)
    def _acc():
        out_ref[...] += table_ref[...].astype(out_ref.dtype)

    if mean:
        @pl.when(l == n_slots - 1)
        def _norm():
            cnt = jnp.maximum(counts_ref[b], 1).astype(out_ref.dtype)
            out_ref[...] /= cnt


@functools.partial(jax.jit, static_argnames=("mean", "interpret"))
def embedding_bag_kernel(table: jnp.ndarray, ids: jnp.ndarray, *,
                         mean: bool = False,
                         interpret: bool) -> jnp.ndarray:
    """table: (V, D); ids: (B, L) int32, -1 padded -> (B, D)."""
    bsz, n_slots = ids.shape
    v, d = table.shape
    counts = jnp.sum((ids >= 0).astype(jnp.int32), axis=1)

    kernel = functools.partial(_bag_kernel, n_slots=n_slots, mean=mean)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,                     # ids, counts ride in SMEM
        grid=(bsz, n_slots),
        in_specs=[
            # table row chosen by the prefetched id (clamped for padding)
            pl.BlockSpec(
                (1, d),
                lambda b, l, ids_ref, counts_ref:
                    (jnp.maximum(ids_ref[b, l], 0), 0)),
        ],
        out_specs=pl.BlockSpec((1, d), lambda b, l, ids_ref, counts_ref:
                               (b, 0)),
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((bsz, d), table.dtype),
        interpret=interpret,
    )(ids, counts, table)
