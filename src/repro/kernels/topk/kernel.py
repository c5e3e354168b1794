"""Pallas TPU kernel: blocked top-k selection (the k knob's select step).

Two-stage selection over dense stage-1 scores (DESIGN.md §3):

  stage 1 (this kernel): each (query, score-block) grid cell extracts its
  local top-k' by iterative max-extraction — k' rounds of vector max +
  masked knockout, entirely in VMEM/VPU registers.  The global top-k is
  provably contained in the union of per-block top-k' **iff k <= k'**
  (one block may hold up to k of the global top-k; any weaker condition
  — in particular "k >= block size" with k' < block size — silently
  drops candidates).  The kernel supports k' <= KP_MAX = 128, so exact
  selection wider than 128 takes the sort path, a route the serving
  engine decides once per program (``retrieval.topk.pool_route``);
  ``block_topk`` itself rejects an out-of-range k' rather than return a
  wrong pool.

  stage 2 (ops.py): a single jnp top_k over the (n_blocks * k') surviving
  candidates — tiny compared to the original score vector.

This mirrors how the candidate universe shards over the mesh at serve
time: stage 1 runs on each model-parallel shard's local scores, stage 2 is
the cross-shard merge.

Iterative extraction (not a bitonic network) is the right TPU shape for
the cascade's hot classes: predicted k is 20-2000, so k' <= 128 rounds of
(8, 128)-lane max is cheap and needs no cross-lane shuffles.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

__all__ = ["KP_MAX", "ROWS", "block_topk"]

NEG_INF = -jnp.inf

#: widest per-block selection the iterative-extraction kernel supports —
#: beyond this the containment guarantee must come from the oracle path
KP_MAX = 128

#: query rows per grid cell (one (8, 128) tile's sublanes); each cell
#: writes one (ROWS, KP_MAX) tile of values and one of indices
ROWS = 8


def _topk_kernel(scores_ref, vals_ref, idxs_ref, *, kp: int, block_n: int):
    bi = pl.program_id(1)
    s = scores_ref[...].astype(jnp.float32)          # (ROWS, block_n)
    base = bi * block_n
    # deterministic ties: prefer lower doc id => lowest-index argmax
    local_idx = jax.lax.broadcasted_iota(jnp.int32, (ROWS, block_n), 1)
    lane = jax.lax.broadcasted_iota(jnp.int32, (ROWS, KP_MAX), 1)

    def body(j, carry):
        s_cur, vals, idxs = carry
        m = jnp.max(s_cur, axis=1, keepdims=True)             # (ROWS, 1)
        # argmax with lowest-index tie-break
        amax = jnp.min(jnp.where(s_cur == m, local_idx, block_n), axis=1,
                       keepdims=True)
        # round j's winner lands in lane j of register-resident outputs:
        # a full-tile select, not a dynamic single-lane store
        vals = jnp.where(lane == j, m, vals)
        idxs = jnp.where(lane == j, base + amax, idxs)
        s_cur = jnp.where(local_idx == amax, NEG_INF, s_cur)
        return s_cur, vals, idxs

    init = (s, jnp.full((ROWS, KP_MAX), NEG_INF, jnp.float32),
            jnp.zeros((ROWS, KP_MAX), jnp.int32))
    _, vals, idxs = jax.lax.fori_loop(0, kp, body, init)
    vals_ref[...] = vals
    idxs_ref[...] = idxs


@functools.partial(
    jax.jit, static_argnames=("kp", "block_n", "interpret"))
def block_topk(scores: jnp.ndarray, *, kp: int, block_n: int = 4096,
               interpret: bool):
    """scores: (Q, N) -> (vals (Q, n_blocks*kp), idxs (Q, n_blocks*kp)).

    Per-block top-kp candidates; the caller merges (ops.topk_select) and
    may only trust the merged global top-k for k <= kp.  kp outside
    [1, KP_MAX] raises — a wider kp breaks the kernel's register-resident
    extraction budget and callers who need k > KP_MAX must route pool
    selection to the sort path (``retrieval.topk.pool_route``), never to
    a silently-wrong block union.  ``interpret`` has no default: the
    caller states whether the body runs compiled or interpreted.

    Each grid cell takes ROWS queries x one score block and writes a
    (ROWS, KP_MAX) tile per output; lanes past ``kp`` are sliced off.
    """
    if not 1 <= kp <= KP_MAX:
        raise ValueError(
            f"block_topk kp must be in [1, {KP_MAX}], got {kp}; the "
            "global top-k is only contained in the per-block unions for "
            f"k <= kp, and kp > {KP_MAX} exceeds the kernel's iterative-"
            "extraction budget — route wider selections to the sort "
            "path (retrieval.topk.pool_route)")
    qn, n = scores.shape
    bn = min(block_n, n)
    n_b = -(-n // bn)
    q_pad = -(-qn // ROWS) * ROWS
    scores = jnp.pad(scores, ((0, q_pad - qn), (0, n_b * bn - n)),
                     constant_values=NEG_INF)

    kernel = functools.partial(_topk_kernel, kp=kp, block_n=bn)
    tile = pl.BlockSpec((ROWS, KP_MAX), lambda g, b: (g, b))
    vals, idxs = pl.pallas_call(
        kernel,
        grid=(q_pad // ROWS, n_b),
        in_specs=[pl.BlockSpec((ROWS, bn), lambda g, b: (g, b))],
        out_specs=[tile, tile],
        out_shape=[
            jax.ShapeDtypeStruct((q_pad, n_b * KP_MAX), jnp.float32),
            jax.ShapeDtypeStruct((q_pad, n_b * KP_MAX), jnp.int32),
        ],
        interpret=interpret,
    )(scores)

    def trim(x):
        return x[:qn].reshape(qn, n_b, KP_MAX)[..., :kp].reshape(
            qn, n_b * kp)

    return trim(vals), trim(idxs)
