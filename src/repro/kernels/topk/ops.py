"""jit'd two-stage top-k: Pallas block select + jnp merge."""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.topk.kernel import block_topk
from repro.kernels.topk.ref import top_k_lowest_index, topk_ref

__all__ = ["topk_select"]


@functools.partial(jax.jit,
                   static_argnames=("k", "block_n", "use_kernel", "interpret"))
def topk_select(scores: jnp.ndarray, k: int, *, block_n: int = 4096,
                use_kernel: bool = True, interpret: bool):
    """Exact top-k of (Q, N) scores; ties broken toward lower index.

    The kernel path covers k <= KP_MAX (the cascade's hot classes) and
    raises beyond it: which path a wider selection takes is decided by
    the caller (``retrieval.topk.pool_route``), never here.
    """
    if not use_kernel:
        return topk_ref(scores, k)
    vals, idxs = block_topk(scores, kp=k, block_n=block_n,
                            interpret=interpret)
    # stage 2: merge the per-block survivors.  They sit block by block,
    # each block's in (value desc, index asc) order, and blocks cover
    # ascending index ranges — so among equal values a lower position
    # is a lower index, and the lowest-position merge is the
    # (score desc, index asc) order
    v, pos = top_k_lowest_index(vals, k)
    return v, jnp.take_along_axis(idxs, pos, axis=1)
