"""Pure-jnp top-k with low-doc-id tie-breaking: the lexsort oracle and the
``lax.top_k`` form the serving path runs."""

from __future__ import annotations

import jax
import jax.numpy as jnp

__all__ = ["top_k_lowest_index", "topk_ref"]


def topk_ref(scores: jnp.ndarray, k: int):
    """scores: (Q, N) -> (vals (Q, k), idxs (Q, k)), ties to lower index."""
    n = scores.shape[-1]

    def one(s):
        order = jnp.lexsort((jnp.arange(n), -s))
        top = order[:k]
        return s[top], top.astype(jnp.int32)

    return jax.vmap(one)(scores)


def top_k_lowest_index(x: jnp.ndarray, k: int):
    """Top-``k`` along the last axis in (value desc, index asc) order —
    ``topk_ref``'s result — on every backend.

    ``lax.top_k`` returns the right values everywhere, but the order of
    equal values, and which of several tied elements at the k-th value
    make the cut, is the backend's: XLA:CPU keeps the lowest index, a TPU
    over a few million columns does not.  So its values only fix the
    threshold ``t`` (the k-th value).  A second ``top_k`` runs over
    distinct keys that rank every element above ``t`` first and the
    elements equal to ``t`` next, each group by ascending index — fewer
    than k lie above ``t``, so it takes all of them and the lowest-index
    ties — and a stable sort of those k by value gives the (value desc,
    index asc) order.  Two top-k passes over the row, where a two-key
    sort of the whole row would take minutes to compile on a TPU.

    The keys (at most 2n) are float32 while that is exact (n <= 2**23),
    int32 beyond; ``t`` is the min of the top-k values, not the last
    one: on a TPU the compiler takes several times longer over a slice of
    ``top_k``'s values or over int32 keys."""
    n = x.shape[-1]
    if not 1 <= k <= n or n >= 1 << 30:
        raise ValueError(f"top_k_lowest_index: k={k} outside [1, {n}] "
                         "or the row too long for int32 keys")
    key_dtype = jnp.float32 if 2 * n <= 1 << 24 else jnp.int32
    t = jnp.min(jax.lax.top_k(x, k)[0], axis=-1, keepdims=True)
    rev = (n - jnp.arange(n, dtype=jnp.int32)).astype(key_dtype)   # n .. 1
    key = jnp.where(x > t, n + rev, jnp.where(x == t, rev, 0))
    top = jax.lax.top_k(key, k)[0].astype(jnp.int32)
    idx = n - jnp.where(top > n, top - n, top)
    vals = jnp.take_along_axis(x, idx, axis=-1)
    order = jnp.argsort(-vals, axis=-1, stable=True)
    return (jnp.take_along_axis(vals, order, axis=-1),
            jnp.take_along_axis(idx, order, axis=-1))
