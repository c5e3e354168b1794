"""Per-architecture PartitionSpec rules (DP / TP / EP / FSDP / SP).

The mesh is ('data', 'model') single-pod or ('pod', 'data', 'model')
multi-pod; batch always shards over all data-parallel axes
(``dp_axes(mesh)``), tensor/expert parallelism over 'model'.

``fsdpify`` is the generic ZeRO-3-style annotator: it adds the data axes to
the first still-unsharded dimension whose size divides, which is how the
671B deepseek config fits 16 GB HBM (params 2.4 GB/device bf16 + fp32
moments via zero1).  XLA GSPMD inserts the all-gathers at use sites and
overlaps them with compute (latency-hiding scheduler).
"""

from __future__ import annotations

from typing import Any

import os

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding
from jax.sharding import PartitionSpec as P

__all__ = ["dp_axes", "dp_axis_spec", "stream_shard_spec", "fsdpify",
           "lm_param_specs",
           "lm_opt_specs", "sage_param_specs", "recsys_param_specs",
           "tree_shardings", "batch_specs_lm", "MeshInfo",
           "make_compat_mesh", "compat_shard_map"]


def make_compat_mesh(axis_shapes, axis_names, devices=None) -> Mesh:
    """``jax.make_mesh`` with every axis pinned to ``AxisType.Auto`` (the
    sharding rules here rely on GSPMD propagation, not explicit-mode
    axes).  ``devices`` defaults to all of ``jax.devices()``."""
    return jax.make_mesh(
        axis_shapes, axis_names,
        axis_types=(jax.sharding.AxisType.Auto,) * len(axis_names),
        devices=devices)


def compat_shard_map(f, mesh: Mesh, in_specs, out_specs):
    """``jax.shard_map`` with replication checking off (the serving
    bodies return per-shard blocks that the checker cannot type)."""
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)


def dp_axes(mesh: Mesh) -> tuple[str, ...]:
    return tuple(a for a in mesh.axis_names if a in ("pod", "data"))


def dp_axis_spec(mesh: Mesh):
    """The PartitionSpec *entry* for a batch dimension: every
    data-parallel axis of the mesh (None when the mesh has none) — the
    serving engine shards request batches with ``P(dp_axis_spec(mesh),
    ...)`` while candidates shard over 'model'."""
    dp = dp_axes(mesh)
    if not dp:
        return None
    return dp if len(dp) > 1 else dp[0]


def stream_shard_spec(mesh: Mesh, axis: str = "model") -> P:
    """PartitionSpec of a doc-range-partitioned per-query stream: batch
    over the data-parallel axes, stream columns over the doc shard axis
    (each shard holds only the postings/scores of docs it owns — the
    serving engine's partitioned layout, vs the old replicated streams)."""
    return P(dp_axis_spec(mesh), axis)


class MeshInfo:
    def __init__(self, mesh: Mesh):
        self.mesh = mesh
        self.dp = dp_axes(mesh)
        self.dp_size = int(np.prod([mesh.shape[a] for a in self.dp]))
        self.tp = mesh.shape.get("model", 1)


def fsdpify(spec: P, shape: tuple[int, ...], mesh: Mesh,
            min_size: int = 2 ** 16) -> P:
    """Add the dp axes to the first unsharded, divisible dim of ``spec``.

    Small tensors (< min_size elements) are left alone — sharding them
    costs more in collective latency than it saves in bytes.
    """
    if int(np.prod(shape)) < min_size:
        return spec
    dp = dp_axes(mesh)
    dp_n = int(np.prod([mesh.shape[a] for a in dp]))
    parts = list(spec) + [None] * (len(shape) - len(spec))
    # already FSDP'd (idempotence: opt-state widening re-applies this)
    flat = [a for p in parts if p is not None
            for a in (p if isinstance(p, tuple) else (p,))]
    if any(a in flat for a in dp):
        return spec
    for i, (s, dim) in enumerate(zip(parts, shape)):
        if s is None and dim % dp_n == 0 and dim >= dp_n:
            parts[i] = dp if len(dp) > 1 else dp[0]
            return P(*parts)
    return spec


def _map_with_path(params: Any, fn) -> Any:
    """tree_map passing the joined key path string."""
    def visit(path, leaf):
        keys = []
        for p in path:
            if hasattr(p, "key"):
                keys.append(str(p.key))
            elif hasattr(p, "idx"):
                keys.append(str(p.idx))
        return fn("/".join(keys), leaf)
    return jax.tree_util.tree_map_with_path(visit, params)


# ------------------------------------------------------------------- LM --

def lm_param_specs(params: Any, mesh: Mesh, *, fsdp: bool = True) -> Any:
    """Megatron-style TP + optional FSDP for the transformer LM family."""

    def rule(path: str, leaf) -> P:
        shape = leaf.shape
        last = path.rsplit("/", 1)[-1]
        if last == "embed":
            spec = P(None, "model")
        elif last == "lm_head":
            spec = P(None, "model")                       # vocab-parallel
        elif last in ("w_gate", "w_up", "ff1", "shared_gate", "shared_up"):
            spec = P(*([None] * (len(shape) - 1)), "model")   # col-parallel
        elif last in ("w_down", "ff2", "shared_down"):
            # row-parallel: contracting dim sharded
            spec = P(*([None] * (len(shape) - 2)), "model", None)
        elif last in ("wq", "wk", "wv", "wo", "wdq", "wuq", "wdkv",
                      "wuk", "wuv", "bq", "bk", "bv"):
            # attention runs sequence-parallel over 'model' (DESIGN §6):
            # projections replicate over model (FSDP'd over data), queries
            # stay seq-sharded end to end, KV replicates (it's small).
            spec = P(*([None] * len(shape)))
        elif last == "router":
            spec = P(*([None] * len(shape)))
        else:
            spec = P(*([None] * len(shape)))              # norms, small proj
        # MoE expert-parallel overrides: (L, E, D, F) tensors with E
        # divisible by the model axis shard experts instead of features.
        if last in ("w_gate", "w_up", "w_down") and len(shape) == 4:
            tp = mesh.shape.get("model", 1)
            dp = dp_axes(mesh)
            dp_n = int(np.prod([mesh.shape[a] for a in dp]))
            ep2d = os.environ.get("REPRO_MOE_EP2D", "0") == "1"
            if ep2d and shape[1] % (tp * dp_n) == 0:
                # §Perf iter D1: experts over model AND data — weights
                # permanently local (no FSDP all-gathers, no contracting-
                # dim partial sums); tokens move via all-to-all instead.
                return P(None, ("model",) + dp, None, None)
            if shape[1] % tp == 0 and shape[1] >= tp:
                spec = P(None, "model", None, None)       # EP
            elif os.environ.get("REPRO_MOE_TPF", "0") == "1":
                # §Perf iter M1: FSDP 'data' must not land on the
                # contracting dim (partial-sum all-reduce per use); shard
                # the f dim over both axes instead (Megatron TP widened)
                return (P(None, None, None, ("model", "data"))
                        if last != "w_down"
                        else P(None, None, ("model", "data"), None))
            else:
                spec = (P(None, None, None, "model")
                        if last != "w_down" else P(None, None, "model", None))
        if fsdp:
            spec = fsdpify(spec, shape, mesh)
        return spec

    return _map_with_path(params, rule)


def lm_opt_specs(param_specs: Any, params: Any, mesh: Mesh,
                 zero1: bool = True) -> dict:
    """Optimizer-state specs: follow params; zero1 additionally spreads
    moments over dp (fsdpify already did if params are FSDP)."""

    def widen(spec_and_leaf):
        spec, leaf = spec_and_leaf
        return fsdpify(spec, leaf.shape, mesh) if zero1 else spec

    m_specs = jax.tree.map(lambda s, p: widen((s, p)), param_specs, params)
    return {"m": m_specs, "v": m_specs, "step": P()}


def batch_specs_lm(mesh: Mesh) -> P:
    dp = dp_axes(mesh)
    return P(dp if len(dp) > 1 else dp[0])


# ------------------------------------------------------------------ GNN --

def sage_param_specs(params: Any, mesh: Mesh) -> Any:
    """GraphSAGE weights are small: replicate (edge work is what shards)."""
    return jax.tree.map(lambda leaf: P(*([None] * leaf.ndim)), params)


# --------------------------------------------------------------- recsys --

def recsys_param_specs(params: Any, mesh: Mesh, *, fsdp: bool = True) -> Any:
    """Column-shard embedding tables over 'model' when dim divides;
    tensor-parallel the wide MLPs; replicate the small recurrent cells."""
    tp = mesh.shape.get("model", 1)

    def rule(path: str, leaf) -> P:
        shape = leaf.shape
        last = path.rsplit("/", 1)[-1]
        if "table" in last or last == "items":
            # (V, D) or (F, V, D): shard last dim if divisible, else rows
            if shape[-1] % tp == 0 and shape[-1] >= tp:
                spec = P(*([None] * (len(shape) - 1)), "model")
            elif shape[0] % tp == 0 and shape[0] >= tp:
                spec = P("model", *([None] * (len(shape) - 1)))
            else:
                spec = P(*([None] * len(shape)))
        elif last == "w" and len(shape) == 2 and shape[1] % tp == 0 \
                and shape[1] >= tp and int(np.prod(shape)) >= 2 ** 16:
            spec = P(None, "model")
        else:
            spec = P(*([None] * len(shape)))
        if fsdp:
            spec = fsdpify(spec, shape, mesh)
        return spec

    return _map_with_path(params, rule)


# ---------------------------------------------------------------- misc --

def tree_shardings(mesh: Mesh, spec_tree: Any) -> Any:
    return jax.tree.map(
        lambda s: NamedSharding(mesh, s),
        spec_tree, is_leaf=lambda x: isinstance(x, P))
