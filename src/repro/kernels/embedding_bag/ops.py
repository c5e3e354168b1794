"""jit'd EmbeddingBag wrapper with kernel/oracle dispatch."""

from __future__ import annotations

import jax.numpy as jnp

from repro.kernels.embedding_bag.kernel import embedding_bag_kernel
from repro.kernels.embedding_bag.ref import embedding_bag_ref

__all__ = ["embedding_bag"]


def embedding_bag(table: jnp.ndarray, ids: jnp.ndarray, *,
                  combiner: str = "sum", use_kernel: bool = True,
                  interpret: bool) -> jnp.ndarray:
    """table (V, D), ids (B, L) -1-padded -> (B, D)."""
    mean = combiner == "mean"
    if use_kernel:
        return embedding_bag_kernel(table, ids, mean=mean,
                                    interpret=interpret)
    return embedding_bag_ref(table, ids, mean=mean)
