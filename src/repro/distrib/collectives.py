"""shard_map collective helpers.

``sharded_topk`` — the distributed form of the paper's k knob: candidates
(items/documents) are row-sharded over an axis; each shard extracts its
local top-k and only (k values + global ids) per shard cross the
interconnect, replacing XLA's default gather-everything lowering.  This is
the two-stage structure of kernels/topk lifted to the mesh (stage 1 =
per-shard, stage 2 = merge after an all-gather of k-sized survivors).

Correctness contract (the sharded serving engine builds on it):

* the local top-k is clamped to the shard width, so ``k`` may exceed
  ``N // n_shards`` (the merge still sees >= k survivors because
  ``n_shards * min(k, width) >= min(k, N_padded)``);
* ``N % n_shards != 0`` is handled by padding the candidate dim with
  sentinel (-inf) columns *before* sharding, so every global id is the
  true row offset — padded ids (>= N) can only surface when k exceeds
  the real candidate count;
* ties break deterministically toward the **lowest global id**, on every
  backend (``kernels.topk.ref.top_k_lowest_index``), so the merged
  ranking is bit-identical to the unsharded oracle.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import Mesh
from jax.sharding import PartitionSpec as P

from repro.kernels.topk.ref import top_k_lowest_index

__all__ = ["sharded_topk", "merge_local_topk", "gather_local_topk",
           "merge_gathered_topk", "require_axis"]


def require_axis(mesh: Mesh, axis: str, what: str = "sharded_topk") -> int:
    """Validate that ``axis`` names a mesh axis; returns its size.

    A mesh without the requested axis used to surface as a bare
    ``KeyError`` from ``mesh.shape[axis]`` deep inside a traced function —
    raise the actionable error at the API boundary instead.
    """
    if axis not in mesh.shape:
        raise ValueError(
            f"{what}: axis {axis!r} is not an axis of the mesh "
            f"(axes: {tuple(mesh.axis_names)}). Pass axis=<one of those> "
            "or build the mesh with the expected axis name.")
    return int(mesh.shape[axis])


def gather_local_topk(v: jnp.ndarray, gi: jnp.ndarray, axis: str):
    """The collective half of ``merge_local_topk``: all-gather every
    shard's (B, kl) survivors into flat (B, S*kl) value/id matrices.

    Split out so the serving engine can *issue* the all-gather as its own
    dispatch and overlap the interconnect time with stage-2 compute
    before running the arithmetic half (``merge_gathered_topk``)."""
    vs = jax.lax.all_gather(v, axis, axis=1)        # (B, S, kl)
    gs = jax.lax.all_gather(gi, axis, axis=1)
    b = v.shape[0]
    return vs.reshape(b, -1), gs.reshape(b, -1)


def merge_gathered_topk(vflat: jnp.ndarray, gflat: jnp.ndarray, k: int):
    """The arithmetic half of ``merge_local_topk``: merge the gathered
    survivors (value desc, global id asc) down to the top-k.

    A lowest-position top-k over the flat values suffices — no lexsort —
    because of how ``gather_local_topk`` lays the survivors out: within a
    shard's block they arrive value-desc with ties id-asc (the per-shard
    top-k's lowest-index rule over id-ordered candidates), and the
    blocks are concatenated in ascending doc-range order, so every run of
    tied values is already in ascending global id across the whole row.
    The lowest-*position* tie rule therefore picks lowest global id,
    bit-identical to the lexsort merge at a fraction of the cost
    (XLA:CPU sorts are comparator-driven and dominate the merge).

    Returns (values (B, k), ids (B, k)), padded with (-inf, -1) in the
    impossible case that fewer than k survivors exist globally."""
    take = min(k, vflat.shape[1])
    mv, pos = top_k_lowest_index(vflat, take)
    mg = jnp.take_along_axis(gflat, pos, axis=1)
    if take < k:
        pad = ((0, 0), (0, k - take))
        mv = jnp.pad(mv, pad, constant_values=-jnp.inf)
        mg = jnp.pad(mg, pad, constant_values=-1)
    return mv, mg


def merge_local_topk(v: jnp.ndarray, gi: jnp.ndarray, k: int, axis: str):
    """Merge per-shard top-k survivors into the global top-k.

    Call **inside** a shard_map body: ``v``/``gi`` are one shard's local
    top-``kl`` values and *global* candidate ids, shapes (B, kl).  Only
    these survivors cross the interconnect (2 * B * kl * n_shards words).
    Ties break toward the lowest global id — bit-identical to an
    unsharded ``top_k_lowest_index``, because each shard's survivors are
    already its lowest-id tied prefix.

    Composition of ``gather_local_topk`` + ``merge_gathered_topk`` (the
    engine's overlapped serve path calls the halves separately).

    Returns (values (B, k), ids (B, k)), padded with (-inf, -1) in the
    impossible case that fewer than k survivors exist globally.
    """
    vflat, gflat = gather_local_topk(v, gi, axis)
    return merge_gathered_topk(vflat, gflat, k)


def sharded_topk(mesh: Mesh, scores: jnp.ndarray, k: int,
                 axis: str = "model"):
    """Top-k over (B, N) scores whose N dim is sharded over ``axis``.

    Returns (values (B, k), global indices (B, k) int32), bit-identical
    to ``top_k_lowest_index(scores, k)`` including tie order (lowest id
    wins).
    Collective volume: 2 * B * min(k, width) * n_shards words instead of
    B * N.
    """
    n = scores.shape[-1]
    n_shards = require_axis(mesh, axis)
    if not 1 <= k <= n:
        raise ValueError(f"sharded_topk: k={k} outside [1, N={n}]")
    pad = (-n) % n_shards
    if pad:
        # uneven N: sentinel columns keep shards equal-width while global
        # ids stay true row offsets; sentinels lose every comparison
        sentinel = (-jnp.inf if jnp.issubdtype(scores.dtype, jnp.floating)
                    else jnp.iinfo(scores.dtype).min)
        scores = jnp.pad(scores, ((0, 0), (0, pad)),
                         constant_values=sentinel)
    width = (n + pad) // n_shards
    kl = min(k, width)                 # local k clamped to shard width

    def local(s):
        # s: (B, width) local block
        v, i = top_k_lowest_index(s, kl)
        base = jax.lax.axis_index(axis) * width
        gi = (i + base).astype(jnp.int32)
        return merge_local_topk(v, gi, k, axis)

    out_spec = P(None, None)
    from repro.distrib.sharding import compat_shard_map
    f = compat_shard_map(
        local, mesh=mesh,
        in_specs=P(None, axis),
        out_specs=(out_spec, out_spec),
    )
    return f(scores)
