"""Single-dispatch bucketed serving engine.

The seed server ran stages 1-3 once *per class bucket*: every distinct
predicted k/rho value re-gathered the posting streams, re-materialized the
(Q, n_docs) stage-2 accumulators, and compiled a fresh XLA executable
(static rho / static pool width).  That makes the dynamic-parameter
machinery scale with the number of live buckets — the opposite of the
paper's efficiency argument (cf. Mackenzie et al., arXiv:1704.03970:
bucketed execution only pays when per-bucket overhead is amortized).

This engine issues a *constant* number of dispatches per batch:

  gather   — posting streams + stage-2 score streams, once per batch
  stage1   — accumulate with a traced (Q,) rho mask (all rho buckets in
             one executable) and select the candidate pool at a static
             max-k, masked per query by a traced pool-width vector (all k
             buckets in one executable)
  stage2   — second-stage scores of the pool's docs, from the query's
             gathered score postings (no n_docs-wide array)
  rerank   — final list from the per-query pool and its scores

The predicted parameter enters every stage as *data* (a traced vector),
never as a static argument, so the executable count is O(1) per padded
batch shape instead of O(unique predicted params).  Executables are
AOT-compiled and cached keyed by input shapes; ``warmup`` pre-compiles
the configured pad-multiple grid at server init.  ``n_compiles`` is the
jit-cache probe the compile-count regression test reads.

Kernel routing: on TPU, accumulation goes through the Pallas
``impact_scan`` kernel — with the predicted ρ as a *traced scalar-
prefetch operand*, so the kernel itself stops early per (query,
posting-block) grid cell, and with the gather stage's per-block doc-id
bounds gating the (posting, doc)-block grid — and pool selection through
``kernels/topk`` (``use_kernel=None`` auto-detects TPU;
``REPRO_FORCE_KERNEL=1`` forces the kernel path in interpret mode so CI
executes the Pallas bodies).  Elsewhere the jnp oracles run; both paths
are bit-identical to the per-bucket reference path
(``pipeline.serve_batch_reference``).

One deliberate behavior change vs the seed: stage-2 noise qids are the
query's batch position everywhere.  The seed's per-bucket path restarted
qids at 0 inside each bucket, so the same query drew *different* stage-2
noise in the dynamic vs fixed paths (and depending on its bucket's
composition); both paths now score a given query identically, and the
reference path was updated to match.
"""

from __future__ import annotations

import dataclasses
import functools
import math
import os
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P

from repro import obs as obs_lib
from repro.kernels.topk.ref import top_k_lowest_index
from repro.retrieval import gold, jass
from repro.retrieval import topk as topk_lib
from repro.retrieval.index import (block_doc_bounds, partition_cap,
                                   partition_postings,
                                   partition_scored_postings)
from repro.serving import bucketing

__all__ = ["SchedPrograms", "SchedState", "ServingEngine",
           "ShardedSchedPrograms", "ShardedServingEngine"]


def named(fn, name: str):
    """``fn`` under ``name``: ``jax.jit`` names the module it lowers
    after the function (a bare ``functools.partial`` lowers as
    ``jit__unknown``)."""
    f = functools.partial(fn)
    f.__name__ = f.__qualname__ = name
    return f


class _PendingCompile:
    """In-flight marker in the executable cache (see ``_compiled``)."""

    def __init__(self):
        self.ready = threading.Event()
        self.exe = None
        self.err: BaseException | None = None


def _pad_ranked(ranked: np.ndarray, depth: int) -> np.ndarray:
    """Pad a ranked matrix out to ``depth`` columns with the explicit
    ``-1`` no-document sentinel (the same value rerank_pool emits for
    exhausted pools), so every serve path returns a fixed
    ``(n, rerank_depth)`` shape.

    Reachable only when the candidate pool is *narrower* than the final
    list — ``ServingConfig`` forbids that on the k knob's shared pool
    (``rerank_depth <= max(cutoffs)``), so in practice this fires on the
    per-bucket reference path and on ``serve_fixed`` calls whose fixed
    param is below ``rerank_depth``.  Tested in
    tests/test_serving_engine.py::test_ranked_pad_is_explicit_sentinel.
    """
    if ranked.shape[1] >= depth:
        return ranked
    pad = depth - ranked.shape[1]
    return np.pad(ranked, ((0, 0), (0, pad)), constant_values=-1)


# --------------------------------------------------------------- stages --
# Module-level so the engine's AOT cache keys stay stable; static config
# enters via functools.partial, per-query parameters stay traced.

def _stage_gather(offsets, pdoc, pimp, pscore, qt, *, cap: int,
                  block_p: int, n_docs: int, with_bounds: bool):
    ds, im = jass.gather_streams(offsets, pdoc, pimp, qt, cap=cap)
    if with_bounds:
        # segment metadata for the impact_scan skips: per-posting-block
        # min/max doc id of the just-materialized streams (exhausted
        # blocks carry the empty interval and are never executed by the
        # kernel)
        seg_lo, seg_hi = block_doc_bounds(ds, block_p=block_p,
                                          n_docs=n_docs)
    else:
        # oracle path ignores the bounds; ship inert (Q, 1) placeholders
        # instead of paying the per-batch reduction for nothing
        seg_lo = seg_hi = jnp.zeros((qt.shape[0], 1), jnp.int32)
    sdocs, s3 = jass.gather_score_streams(offsets, pdoc, pscore, qt,
                                          cap=cap)
    return ds, im, seg_lo, seg_hi, sdocs, s3


def _stage1_rho(ds, im, seg_lo, seg_hi, rho_vec, *, n_docs: int,
                depth: int, use_kernel: bool, interpret: bool,
                block_p: int, block_d: int, route: str):
    acc = jass.saat_scores_masked(ds, im, rho_vec, n_docs,
                                  use_kernel=use_kernel,
                                  interpret=interpret,
                                  seg_bounds=(seg_lo, seg_hi),
                                  block_p=block_p, block_d=block_d)
    return topk_lib.select_pool(acc, depth, route=route,
                                interpret=interpret)


def _stage1_k(ds, im, seg_lo, seg_hi, k_vec, *, n_docs: int, max_k: int,
              use_kernel: bool, interpret: bool, block_p: int,
              block_d: int, route: str):
    # exhaustive stage-1 scores (rho = P), one shared max-k selection;
    # the per-query pool width is a traced mask over the shared pool
    full = jnp.full(ds.shape[:1], ds.shape[-1], jnp.int32)
    acc = jass.saat_scores_masked(ds, im, full, n_docs,
                                  use_kernel=use_kernel,
                                  interpret=interpret,
                                  seg_bounds=(seg_lo, seg_hi),
                                  block_p=block_p, block_d=block_d)
    pool = topk_lib.select_pool(acc, max_k, route=route,
                                interpret=interpret)
    keep = jnp.arange(pool.shape[-1])[None, :] < k_vec[:, None]
    return jnp.where(keep, pool, -1)


def _stage_rerank(scores, pool, *, depth: int):
    return gold.rerank_scored(scores, pool, depth)


def _depth_mask(pool, depth_vec):
    """The depth knob's traced mask: restrict stage 2 to each query's
    top-``depth_vec[q]`` stage-1 candidates.  The pool is rank-ordered
    (select_pool emits descending stage-1 score), so a prefix mask *is*
    the scored-depth bound — the exact idiom of the k knob's pool-width
    mask, and a no-op when depth_vec equals the static pool width (the
    knob's reference), which is what keeps depth==max bit-identical to
    the depth-free executables."""
    keep = jnp.arange(pool.shape[-1])[None, :] < depth_vec[:, None]
    return jnp.where(keep, pool, -1)


def _stage_rerank_dyn(scores, pool, depth_vec, *, depth: int):
    """``_stage_rerank`` with a traced per-query reranking depth: the
    third knob.  Static shapes are identical to the depth-free stage
    (one executable per padded shape; the depth enters as data)."""
    return gold.rerank_scored(scores, _depth_mask(pool, depth_vec), depth)


# ----------------------------------------------------- scheduler stages --
# The continuous scheduler's four programs.  Same rule as above: static
# geometry (chunk/bounds block sizes, doc counts) via functools.partial,
# everything per-slot — stream positions, remaining rho, slot indices,
# qids — stays a traced operand, so the slot table can churn through any
# admit/retire pattern on exactly these four executables.

def _sched_gather(offsets, pdoc, pimp, pscore, qt, *, cap: int,
                  bounds_p: int, n_docs: int, with_bounds: bool):
    """Per-request slot rows: posting/score streams, segment bounds at the
    *chunk* granularity, and the true stream length (the scheduler's
    ragged-tail retirement bound)."""
    ds, im, seg_lo, seg_hi, sdocs, s3 = _stage_gather(
        offsets, pdoc, pimp, pscore, qt, cap=cap, block_p=bounds_p,
        n_docs=n_docs, with_bounds=with_bounds)
    slen = jnp.sum(ds >= 0, axis=-1).astype(jnp.int32)
    return ds, im, seg_lo, seg_hi, sdocs, s3, slen


def _sched_refill(ds_b, im_b, lo_b, hi_b, sd_b, s3_b, acc, slot_idx,
                  ds, im, lo, hi, sd, s3):
    """Install a refill group's gathered rows into its slots and zero the
    accumulator rows.  ``slot_idx`` entries past the table (== capacity)
    are the group's padding and are dropped by the scatter."""
    drop = dict(mode="drop")
    return (ds_b.at[slot_idx].set(ds, **drop),
            im_b.at[slot_idx].set(im, **drop),
            lo_b.at[slot_idx].set(lo, **drop),
            hi_b.at[slot_idx].set(hi, **drop),
            sd_b.at[slot_idx].set(sd, **drop),
            s3_b.at[slot_idx].set(s3, **drop),
            acc.at[slot_idx].set(0.0, **drop))


def _sched_chunk(ds_b, im_b, lo_b, hi_b, acc, pos, end, *, chunk_p: int,
                 bounds_p: int, n_docs: int, use_kernel: bool,
                 interpret: bool, block_d: int):
    """One resumable stage-1 step over the whole slot table: accumulate
    each slot's next ``chunk_p`` postings, masked to its remaining budget
    ``end - pos`` (idle slots carry rho 0 and add exact zeros).

    The chunked partial sums reproduce the batch-once accumulator bit for
    bit: impacts are quantized integer-valued float32, so every scatter-add
    is exact and the split into chunks cannot change the total.
    """
    p = ds_b.shape[-1]
    off = pos[:, None] + jnp.arange(chunk_p, dtype=jnp.int32)[None, :]
    idx = jnp.minimum(off, p - 1)       # clamp idle slots; rho-masked below
    ds = jnp.take_along_axis(ds_b, idx, axis=1)
    im = jnp.take_along_axis(im_b, idx, axis=1)
    rho_rem = jnp.clip(end - pos, 0, chunk_p).astype(jnp.int32)
    if use_kernel:
        nb = chunk_p // bounds_p
        bidx = (pos[:, None] // bounds_p
                + jnp.arange(nb, dtype=jnp.int32)[None, :])
        bidx = jnp.minimum(bidx, lo_b.shape[-1] - 1)
        seg = (jnp.take_along_axis(lo_b, bidx, axis=1),
               jnp.take_along_axis(hi_b, bidx, axis=1))
    else:
        seg = None
    inc = jass.saat_scores_masked(ds, im, rho_rem, n_docs,
                                  use_kernel=use_kernel,
                                  interpret=interpret, seg_bounds=seg,
                                  block_p=bounds_p, block_d=block_d)
    return acc + inc


def _sched_finalize_rho(acc, ds_b, sd_b, s3_b, slot_idx, dvec, qids,
                        doc_len, *, depth: int, n_docs: int, cap: int,
                        route: str, interpret: bool):
    """Stages 1b-3 for a retiring group: pool selection over the finished
    accumulator rows at their slots' stream documents (a top-k over at
    most ``stream_cap`` candidates a row, not over ``n_docs`` columns),
    then stage-2 + rerank exactly as the batch path (qids are the
    request's arrival index, so stage-2 noise matches).

    ``dvec`` is the traced per-slot reranking depth; a scheduler without
    a depth knob passes the static pool width, making the mask a no-op
    (bit-identical to the depth-free program, same executable count)."""
    pool = topk_lib.select_pool_from_stream(acc, slot_idx, ds_b[slot_idx],
                                            depth, route=route,
                                            interpret=interpret)
    scores = gold.pool_stage2_scores(sd_b[slot_idx], s3_b[slot_idx], pool,
                                     doc_len, qids, n_docs=n_docs, cap=cap)
    return gold.rerank_scored(scores, _depth_mask(pool, dvec), depth)


def _sched_finalize_k(acc, ds_b, sd_b, s3_b, slot_idx, k_vec, dvec, qids,
                      doc_len, *, depth: int, max_k: int, n_docs: int,
                      cap: int, route: str, interpret: bool):
    pool = topk_lib.select_pool_from_stream(acc, slot_idx, ds_b[slot_idx],
                                            max_k, route=route,
                                            interpret=interpret)
    keep = jnp.arange(pool.shape[-1])[None, :] < k_vec[:, None]
    pool = jnp.where(keep, pool, -1)
    scores = gold.pool_stage2_scores(sd_b[slot_idx], s3_b[slot_idx], pool,
                                     doc_len, qids, n_docs=n_docs, cap=cap)
    return gold.rerank_scored(scores, _depth_mask(pool, dvec), depth)


class ServingEngine:
    """Owns the AOT executable cache and the staged batch-once pipeline.

    ``serve(query_terms, param_vec)`` runs the four stages over the whole
    (padded) batch and returns (ranked, per-stage timings).
    """

    def __init__(self, index, cfg, *, use_kernel: bool | None = None):
        self.cfg = cfg
        on_tpu = jax.default_backend() == "tpu"
        # REPRO_FORCE_KERNEL=1 forces the Pallas path off-TPU (interpret
        # mode) so CI executes the kernel bodies on every PR
        forced = os.environ.get("REPRO_FORCE_KERNEL") == "1"
        self.use_kernel = ((on_tpu or forced) if use_kernel is None
                           else use_kernel)
        self.interpret = not on_tpu
        self.block_p = cfg.kernel_block_p
        self.block_d = cfg.kernel_block_d
        self.offsets = jnp.asarray(index.offsets)
        self.pdoc = jnp.asarray(index.postings_doc)
        self.pimp = jnp.asarray(index.postings_impact.astype(np.float32))
        self.pscore = jnp.asarray(index.postings_score)
        self.doc_len = jnp.asarray(index.corpus.doc_len)
        self.n_docs = index.corpus.n_docs
        self.max_k = int(max(cfg.cutoffs))
        # the padded-batch grid; the mesh-sharded engine widens it so
        # batches also divide over the data-parallel axes
        self.batch_multiple = cfg.pad_multiple
        self._cache: dict = {}
        self._cache_lock = threading.Lock()
        self.n_compiles = 0
        # observability: spans around dispatch boundaries (never inside
        # traced code) + deterministic dispatch/compile counters.  obs
        # locks are leaves in the global order, so recording under
        # _cache_lock is legal.
        self.trace = obs_lib.NULL_TRACE
        self._m_dispatch = obs_lib.NULL_METRIC
        self._m_compile = obs_lib.NULL_METRIC

        self._kern = dict(use_kernel=self.use_kernel,
                          interpret=self.interpret,
                          block_p=self.block_p, block_d=self.block_d)
        #: pool-selection route per program ("pallas" | "xla"), decided
        #: here once from the backend and the pool width (the Pallas
        #: top-k holds at most KP_MAX per block); the scheduler programs
        #: add "finalize"
        self.topk_routes = {
            "stage1": self.topk_route(cfg.depth_pool_width)}
        self._gather = functools.partial(_stage_gather,
                                         cap=cfg.stream_cap,
                                         block_p=self.block_p,
                                         n_docs=self.n_docs,
                                         with_bounds=self.use_kernel)
        self._stage2 = functools.partial(gold.pool_stage2_scores,
                                         n_docs=self.n_docs,
                                         cap=cfg.stream_cap)
        self._rerank = functools.partial(_stage_rerank,
                                         depth=cfg.rerank_depth)
        self._rerank_dyn = functools.partial(_stage_rerank_dyn,
                                             depth=cfg.rerank_depth)

    def topk_route(self, width: int) -> str:
        """Route of a ``width``-wide pool selection on this engine."""
        return topk_lib.pool_route(width, use_kernel=self.use_kernel)

    def bind_obs(self, obs) -> None:
        """Attach an observability handle: per-stage spans in ``serve``
        and the scheduler programs, plus dispatch/compile counters."""
        self.trace = obs.trace
        self._m_dispatch = obs.metrics.counter("engine.dispatches")
        self._m_compile = obs.metrics.counter("engine.compiles")

    def _stage1_for(self, pool_width: int):
        """stage1 fn + cache name for a given static pool width (the
        shared executable uses ``max_k``; serve_fixed may request wider)."""
        if self.cfg.knob == "rho":
            depth = self.cfg.rerank_depth
            return ("stage1", functools.partial(
                _stage1_rho, n_docs=self.n_docs, depth=depth,
                route=self.topk_route(depth), **self._kern))
        return (f"stage1:{pool_width}", functools.partial(
            _stage1_k, n_docs=self.n_docs, max_k=pool_width,
            route=self.topk_route(pool_width), **self._kern))

    # ------------------------------------------------------ exec cache --
    def _compiled(self, name: str, fn, args, *, scope: str = "engine"):
        """Shape-keyed AOT cache lookup; compiles on miss, as the module
        ``jit_<scope>_<stage>``: the cache name without its static width
        suffix, so the name is the same on every run and carries no
        shape.

        Thread-safe: the service's background warmup thread compiles
        concurrently with the exec thread, so a miss installs a pending
        marker under the lock and exactly one thread compiles each key
        (others block on its event instead of duplicating the compile or
        double-counting ``n_compiles``)."""
        key = (name,) + tuple((a.shape, str(a.dtype)) for a in args)
        owner = False
        with self._cache_lock:
            entry = self._cache.get(key)
            if entry is None:
                entry = self._cache[key] = _PendingCompile()
                owner = True
        if isinstance(entry, _PendingCompile):
            if owner:
                try:
                    stage = name.split(":")[0]
                    exe = jax.jit(named(fn, f"{scope}_{stage}")
                                  ).lower(*args).compile()
                except BaseException as e:
                    with self._cache_lock:
                        self._cache.pop(key, None)
                    entry.err = e
                    entry.ready.set()
                    raise
                with self._cache_lock:
                    self._cache[key] = exe
                    self.n_compiles += 1
                self._m_compile.inc()
                entry.exe = exe
                entry.ready.set()
                return exe
            entry.ready.wait()
            if entry.err is not None:
                raise entry.err
            return entry.exe
        return entry

    def padded_batch(self, n: int) -> int:
        return bucketing.pad_length(n, self.batch_multiple)

    def _place(self, name: str, j: int, x):
        """Hook: device placement for argument ``j`` of stage ``name``
        (the sharded engine commits inputs to their mesh shardings so the
        AOT executables never reshard on the serving path)."""
        del name, j
        return x

    # --------------------------------------------------------- serving --
    def serve(self, query_terms: np.ndarray, param_vec: np.ndarray,
              pool_width: int | None = None,
              depth_vec: np.ndarray | None = None):
        """Batch-once pipeline.  param_vec: (n,) predicted k or rho.

        ``pool_width`` (k knob only) overrides the shared pool's static
        width — serve_fixed uses it to honor fixed params beyond the
        cutoff grid with a dedicated executable instead of a silent clamp.

        ``depth_vec`` (the third knob) is a per-query reranking depth: a
        traced prefix mask over the rank-ordered candidate pool before
        stage-2 rerank.  None keeps the depth-free executables exactly
        as before; a vector dispatches the ``rerank_dyn`` variant (one
        extra executable per padded shape, still O(1) under churn), and
        a vector pinned to the static pool width is bit-identical to
        None.

        Returns (ranked (n, rerank_depth) np.ndarray, timings dict in ms).
        """
        n, qlen = query_terms.shape
        qt = bucketing.pad_rows(np.asarray(query_terms, np.int32),
                                self.batch_multiple, fill=-1)
        pv = bucketing.pad_rows(np.asarray(param_vec, np.int32),
                                self.batch_multiple, fill=1)
        if depth_vec is not None:
            depth_vec = bucketing.pad_rows(
                np.asarray(depth_vec, np.int32), self.batch_multiple,
                fill=1)
        qids = np.arange(qt.shape[0], dtype=np.int32)

        timings = {}

        def timed(label, name, fn, *a):
            # compile (cold shapes only) outside the timed region so the
            # per-stage numbers report steady-state latency, not XLA
            a = tuple(self._place(name, j, jnp.asarray(x))
                      for j, x in enumerate(a))
            exe = self._compiled(name, fn, a)
            self._m_dispatch.inc()
            # one instrumentation path: the timings dict is *derived*
            # from the span (handles carry t0/t1 even with obs off)
            with self.trace.span("engine." + name) as sp:
                out = exe(*a)
                jax.block_until_ready(out)
            timings[label] = sp.dur_ms
            return out

        s1_name, s1_fn = self._stage1_for(int(pool_width or self.max_k))
        ds, im, seg_lo, seg_hi, sdocs, s3 = timed(
            "gather_ms", "gather", self._gather,
            self.offsets, self.pdoc, self.pimp, self.pscore, qt)
        pool = timed("stage1_ms", s1_name, s1_fn, ds, im, seg_lo, seg_hi,
                     pv)
        stage2 = timed("stage2_ms", "stage2", self._stage2,
                       sdocs, s3, pool, self.doc_len, qids)
        if depth_vec is None:
            ranked = timed("rerank_ms", "rerank", self._rerank, stage2,
                           pool)
        else:
            ranked = timed("rerank_ms", "rerank_dyn", self._rerank_dyn,
                           stage2, pool, depth_vec)
        ranked = _pad_ranked(np.asarray(ranked)[:n], self.cfg.rerank_depth)
        return ranked, timings

    def warmup_shape(self, batch_size: int, query_len: int, *,
                     with_depth: bool = False) -> int:
        """Pre-compile the full pipeline for one padded batch size (the
        unit the learned warmup policy requests).  ``with_depth`` also
        compiles the dynamic-depth rerank variant (servers with a depth
        knob pass it so the first depth-predicting batch finds a warm
        executable).  Returns executables compiled (0 when the shape was
        already warm)."""
        with self._cache_lock:
            before = self.n_compiles
        b = self.padded_batch(int(batch_size))
        qt = np.full((b, query_len), -1, np.int32)
        pv = np.ones(b, np.int32)
        self.serve(qt, pv)
        if with_depth:
            self.serve(qt, pv, depth_vec=np.ones(b, np.int32))
        with self._cache_lock:
            return self.n_compiles - before

    def warmup(self, batch_sizes, query_len: int, *,
               with_depth: bool = False) -> int:
        """Pre-compile the pipeline for each padded batch size in
        ``batch_sizes`` (the configured pad-multiple grid).  Returns the
        number of executables compiled."""
        with self._cache_lock:
            before = self.n_compiles
        for b in sorted({self.padded_batch(int(b)) for b in batch_sizes}):
            self.warmup_shape(b, query_len, with_depth=with_depth)
        with self._cache_lock:
            return self.n_compiles - before

    # ----------------------------------------------- continuous serving --
    @property
    def supports_continuous(self) -> bool:
        """Whether ``SchedPrograms``/``ContinuousBackend`` can drive this
        engine (capability check — backends name the missing piece via
        ``continuous_unsupported_reason`` instead of guessing by type)."""
        return True

    @property
    def continuous_unsupported_reason(self) -> str | None:
        return None


# ----------------------------------------------------- mesh-sharded stages --
# Per-shard bodies (run inside shard_map).  The doc/candidate dimension is
# sharded over the 'model' axis, request batches over the data axes.  The
# posting streams are *doc-range partitioned* at gather time
# (``retrieval.index.partition_postings``): each shard keeps only the
# postings of docs it owns, compacted into a ~cap/n_shards-wide local
# stream whose per-posting global stream position (``gpos``) carries the
# rho bookkeeping — ``count(gpos < rho)`` is the shard-local rho prefix,
# so the same traced-rho kernel/oracle path runs on 1/n_shards of the
# stream with no extra masking.  Every (Q, n_docs) accumulator likewise
# shrinks to (Q, n_docs / n_shards) per device, and pool selection sends
# only k-sized survivor lists over the interconnect
# (collectives.gather_local_topk / merge_gathered_topk — split so the
# all-gather overlaps stage-2 compute).  The traced rho-mask /
# pool-width-mask design is unchanged, so the AOT executable count stays
# O(1) per padded batch shape on any mesh.

def _sh_gather(offsets, pdoc, pimp, pscore, qt, *, cap: int,
               shard_cap: int, block_p: int, width: int, axis: str,
               n_shards: int, slack: float, with_bounds: bool):
    """Gather + doc-range partition: this shard's slice of the streams.

    The global impact-ordered streams are materialized exactly as on the
    unsharded path, then split by doc range: owned postings compact into
    a ``shard_cap``-wide local stream (global order preserved, so every
    accumulator addition happens in the unsharded sequence), segment
    bounds are computed on the *local* stream in shard-local coordinates,
    and the stage-2 score streams partition the same way.  The returned
    ``over`` vector is the per-query partition overflow (postings dropped
    because a shard owned more than its slack-capped stream; the engine
    raises on any nonzero — results would silently be wrong otherwise).
    """
    lo = jax.lax.axis_index(axis) * width
    ds, im = jass.gather_streams(offsets, pdoc, pimp, qt, cap=cap)
    ds_l, im_l, gpos, novf = partition_postings(ds, im, lo, width=width,
                                                cap=shard_cap)
    if with_bounds:
        seg_lo, seg_hi = block_doc_bounds(ds_l, block_p=block_p,
                                          n_docs=width)
    else:
        seg_lo = seg_hi = jnp.zeros((qt.shape[0], 1), jnp.int32)
    sdocs, s3 = jass.gather_score_streams(offsets, pdoc, pscore, qt,
                                          cap=cap)
    # static per trace: the score-stream length is L*cap with L the
    # (padded) query width of this executable's shape
    score_cap = partition_cap(sdocs.shape[-1], n_shards, slack)
    sd_l, s3_l, sovf = partition_scored_postings(sdocs, s3, lo,
                                                 width=width,
                                                 cap=score_cap)
    over = jax.lax.pmax(jnp.maximum(novf, sovf), axis)
    return ds_l, im_l, seg_lo, seg_hi, gpos, sd_l, s3_l, over


def _sh_stage1_local(ds_l, im_l, seg_lo, seg_hi, gpos, pvec, *,
                     knob: str, axis: str, width: int, kl: int,
                     use_kernel: bool, interpret: bool, block_p: int,
                     block_d: int, route: str):
    """Local stage 1 over the owned partition: rho-masked accumulation +
    this shard's top-``kl`` survivors (values, global doc ids).

    The global rho budget translates to the local stream through the
    prefix property: ``gpos`` is strictly increasing over the compacted
    owned postings, so the admitted ones are exactly the first
    ``count(gpos < rho)`` — a drop-in rho vector for the unified
    kernel/oracle ``saat_scores_masked`` on local doc ids.  No collective
    runs here; the survivor merge is its own dispatch so its all-gather
    can overlap stage 2."""
    if knob == "rho":
        from repro.kernels.impact_scan.ops import owned_prefix_len
        rho_l = owned_prefix_len(gpos, pvec)
    else:
        # k knob: exhaustive stage-1 scores, budget applied at the pool
        rho_l = jnp.full(ds_l.shape[:1], ds_l.shape[-1], jnp.int32)
    acc = jass.saat_scores_masked(ds_l, im_l, rho_l, width,
                                  use_kernel=use_kernel,
                                  interpret=interpret,
                                  seg_bounds=(seg_lo, seg_hi),
                                  block_p=block_p, block_d=block_d)
    v, i = _local_topk(acc, kl, route=route, interpret=interpret)
    lo = jax.lax.axis_index(axis) * width
    gi = (i + lo).astype(jnp.int32)
    return v, gi


def _local_topk(acc, kl: int, *, route: str, interpret: bool):
    """A shard's top-``kl`` (values, local ids) on the route the engine
    chose (``retrieval.topk.pool_route``): the Pallas blocked top-k or
    ``top_k_lowest_index`` — identical values and lowest-index ties."""
    if route == "pallas":
        from repro.kernels.topk import ops as tk_ops
        return tk_ops.topk_select(acc, kl, interpret=interpret)
    return top_k_lowest_index(acc, kl)


def _sh_allgather(v, gi, *, axis: str):
    """The cross-shard survivor all-gather, as its own dispatch: issued
    asynchronously before stage 2 so the interconnect time hides behind
    the stage-2 accumulator fetch (the lexsort merge runs after)."""
    from repro.distrib import collectives
    return collectives.gather_local_topk(v, gi, axis)


def _sh_merge_rho(vflat, gflat, *, depth: int):
    """The arithmetic half of the pool merge (rho knob): lexsort the
    gathered survivors down to the global top-``depth`` pool."""
    from repro.distrib import collectives
    mv, mg = collectives.merge_gathered_topk(vflat, gflat, depth)
    return jnp.where(mv > 0, mg, -1)


def _sh_merge_k(vflat, gflat, k_vec, *, max_k: int):
    """Pool merge (k knob): shared static-``max_k`` pool, per-query width
    as a traced mask — the sharded form of ``_stage1_k``'s tail."""
    from repro.distrib import collectives
    mv, mg = collectives.merge_gathered_topk(vflat, gflat, max_k)
    pool = jnp.where(mv > 0, mg, -1)
    keep = jnp.arange(pool.shape[-1])[None, :] < k_vec[:, None]
    return jnp.where(keep, pool, -1)


def _pool_from_local(acc, depth: int, *, axis: str, width: int,
                     route: str, interpret: bool):
    """select_pool over doc-sharded accumulators: local top-k clamped to
    the shard width, global ids from the true shard offset, merged with
    lowest-doc-id tie-breaking (bit-identical to rank_from_scores'
    lexsort; padded doc columns score 0.0, sit at the highest global ids,
    and are masked to -1 by the same >0 rule as real zero-score docs).

    The per-shard local scores are exactly the blocked-top-k stage-1
    shape ``kernels/topk`` was designed for, so the "pallas" route runs
    ``topk_select`` (Pallas block extraction + merge; identical values
    and lowest-index ties) where the "xla" route runs
    ``top_k_lowest_index``."""
    from repro.distrib import collectives
    kl = min(depth, width)
    v, i = _local_topk(acc, kl, route=route, interpret=interpret)
    lo = jax.lax.axis_index(axis) * width
    gi = (i + lo).astype(jnp.int32)
    mv, mg = collectives.merge_local_topk(v, gi, depth, axis)
    return jnp.where(mv > 0, mg, -1)


def _sh_stage2(sd_l, s3_l, doc_len, qids, *, axis: str, width: int,
               n_docs: int):
    """Doc-sharded stage 2 over the *partitioned* score streams: local
    scorer accumulators + the second-stage mixture, with per-query
    normalization bounds reduced over the mesh (pmin/pmax of local
    min/max — exact, so bit-identical to the global min/max; padded doc
    columns are masked out of the bounds).

    ``sd_l`` carries shard-local doc ids (-1 on padding) straight from
    ``partition_scored_postings``: the scatter-add touches only owned
    postings — each shard fetches 1/n_shards of the stream instead of
    scanning the full replicated one — and the compaction preserved the
    global addition order, so each accumulator cell sees the unsharded
    sequence of adds bit for bit (dropped non-owned adds were exact +0.0
    at foreign cells and never existed locally)."""
    lo = jax.lax.axis_index(axis) * width
    own = sd_l >= 0
    idx = jnp.clip(sd_l, 0, width - 1)

    def one(i, s, ow):
        z = jnp.zeros((width, 3), jnp.float32)
        return z.at[i].add(jnp.where(ow[:, None], s, 0.0))

    acc = jax.vmap(one)(idx, s3_l, own)          # (Q, width, 3)
    a_bm25, a_lm, a_tfidf = acc[..., 0], acc[..., 1], acc[..., 2]
    gcols = lo + jnp.arange(width)               # global doc ids here
    real = (gcols < n_docs)[None, :]

    def bound(x):
        b_lo = jax.lax.pmin(jnp.min(jnp.where(real, x, jnp.inf),
                                    axis=-1, keepdims=True), axis)
        b_hi = jax.lax.pmax(jnp.max(jnp.where(real, x, -jnp.inf),
                                    axis=-1, keepdims=True), axis)
        return b_lo, b_hi

    return gold.second_stage_mix(
        a_bm25, a_lm, a_tfidf,
        (bound(a_bm25), bound(a_lm), bound(a_tfidf)),
        doc_len, qids, gcols)


def _sh_rerank(stage2, pool, *, axis: str, width: int, depth: int):
    """rerank_pool over doc-sharded stage-2 scores: the owning shard
    contributes each pool member's score, pmax assembles the full (Q, k)
    score matrix (pool ids are tiny — this is the only stage-2 collective),
    then every shard runs the identical lexsort rerank."""
    lo = jax.lax.axis_index(axis) * width
    own = (pool >= lo) & (pool < lo + width)
    s = jnp.where(own,
                  jnp.take_along_axis(
                      stage2, jnp.clip(pool - lo, 0, width - 1), axis=1),
                  -jnp.inf)
    return gold.rerank_scored(jax.lax.pmax(s, axis), pool, depth)


def _sh_rerank_dyn(stage2, pool, depth_vec, *, axis: str, width: int,
                   depth: int):
    """``_sh_rerank`` with the traced per-query reranking depth: the
    prefix mask runs on the replicated pool before the pmax score
    assembly, so masked members never cost a collective word."""
    return _sh_rerank(stage2, _depth_mask(pool, depth_vec), axis=axis,
                      width=width, depth=depth)


class ShardedServingEngine(ServingEngine):
    """The single-dispatch engine over a device mesh.

    Layout: the candidate/doc dimension of every stage-1/stage-2
    accumulator shards over ``axis`` ('model'); request batches shard over
    the data-parallel axes ('pod', 'data').  ``n_docs`` is padded up to a
    multiple of the shard count with inert columns, so uneven shards need
    no special cases and global doc ids are true row offsets.  The
    posting and score streams are *doc-range partitioned* at gather time
    (``stream_shard_spec``: batch over data axes, stream columns over
    ``axis``) — each shard holds a ``shard_cap``-wide compacted stream of
    only the postings it owns (``shard_cap ~= slack * cap / n_shards``,
    ``ServingConfig.partition_slack``), so per-shard gather volume and
    stage-1/-2 stream reads scale ~1/n_shards.  Outputs are bit-identical
    to the unsharded engine (and therefore to
    ``pipeline.serve_batch_reference``) — see the per-stage bodies above
    for why partitioning and each collective preserve exact arithmetic.

    The AOT executable cache, ``warmup``/``warmup_shape``, ``n_compiles``
    and the serve() surface are inherited; ``batch_multiple`` widens the
    pad grid to also divide over the data axes, which
    ``ShardedEngineBackend`` reports as its admission ``pad_multiple``.
    ``serve`` is overridden to *overlap* the cross-shard pool merge with
    stage 2: stage 1 ends at the per-shard survivors, the survivor
    all-gather is issued as its own async dispatch, the stage-2
    accumulator fetch runs while it is in flight, and the lexsort merge
    lands last — six executables per padded shape instead of four, still
    O(1) under churn.

    Kernel routing: the Pallas kernels run *inside* the shard_map stage
    bodies on the kernel path (TPU, or ``REPRO_FORCE_KERNEL=1`` in
    interpret mode).  Each shard hands ``impact_scan`` its partitioned
    local stream — shard-local doc ids, segment bounds computed *on the
    local stream* in local coordinates (so posting blocks a shard does
    not own never enter its grid), and the traced per-query ρ vector
    translated to the local prefix length by ``owned_prefix_len`` — and
    the per-shard local scores feed the blocked top-k kernel
    (``topk_select``), whose survivors the split
    ``gather_local_topk``/``merge_gathered_topk`` pair combines exactly
    as on the oracle path.  Output stays bit-identical to the unsharded
    engine on both paths; see ``_sh_gather``/``_sh_stage1_local`` for
    the argument.
    """

    def __init__(self, index, cfg, mesh, *, axis: str = "model",
                 use_kernel: bool | None = None):
        from repro.distrib import collectives
        from repro.distrib.sharding import (compat_shard_map, dp_axes,
                                            dp_axis_spec)
        super().__init__(index, cfg, use_kernel=use_kernel)
        self.n_shards = collectives.require_axis(
            mesh, axis, what="ShardedServingEngine")
        self.mesh = mesh
        self.axis = axis
        self.dp = dp_axes(mesh)
        self.dp_size = (int(np.prod([mesh.shape[a] for a in self.dp]))
                        if self.dp else 1)
        self.batch_multiple = math.lcm(cfg.pad_multiple, self.dp_size)
        self.doc_pad = bucketing.pad_length(self.n_docs, self.n_shards)
        self.shard_width = self.doc_pad // self.n_shards
        # per-shard partitioned stream width: ~cap/n_shards with slack
        # headroom for skewed doc-range ownership (overflow raises)
        self.shard_cap = partition_cap(cfg.stream_cap, self.n_shards,
                                       cfg.partition_slack)

        dspec = dp_axis_spec(mesh)
        b1, b2 = P(dspec), P(dspec, None)
        pa = P(dspec, axis)          # partitioned per-query stream rows
        #: per-stage input PartitionSpecs (arg order = serve()'s)
        self._specs = {
            "gather": (P(None), P(None), P(None), P(None, None), b2),
            "stage1": (pa, pa, pa, pa, pa, b1),
            "allgather": (pa, pa),
            "merge": (b2, b2, b1),
            "stage2": (pa, P(dspec, axis, None), P(axis), b1),
            "rerank": (P(dspec, axis), b2),
            "rerank_dyn": (P(dspec, axis), b2, b1),
        }
        # commit the static inputs to their mesh shardings once, so the
        # per-call device_put in _place short-circuits instead of
        # re-broadcasting the memory-dominating postings index per batch
        self.offsets = jax.device_put(self.offsets,
                                      NamedSharding(mesh, P(None)))
        self.pdoc = jax.device_put(self.pdoc, NamedSharding(mesh, P(None)))
        self.pimp = jax.device_put(self.pimp, NamedSharding(mesh, P(None)))
        self.pscore = jax.device_put(self.pscore,
                                     NamedSharding(mesh, P(None, None)))
        # doc_len padded to the sharded width and committed to its shard
        dl = np.asarray(index.corpus.doc_len)
        dl = np.pad(dl, (0, self.doc_pad - self.n_docs),
                    constant_values=1)
        self.doc_len = jax.device_put(dl, NamedSharding(mesh, P(axis)))

        def smap(fn, in_specs, out_specs):
            return compat_shard_map(fn, mesh=mesh, in_specs=in_specs,
                                    out_specs=out_specs)

        self._smap = smap
        # a shard selects its local survivors: at most shard_width wide
        self.topk_routes = {"stage1": self.topk_route(
            min(cfg.depth_pool_width, self.shard_width))}
        self._stat = dict(axis=axis, width=self.shard_width)
        self._s1_stat = dict(**self._stat, **self._kern)
        self._gather = smap(
            functools.partial(_sh_gather, cap=cfg.stream_cap,
                              shard_cap=self.shard_cap,
                              block_p=self.block_p,
                              width=self.shard_width, axis=axis,
                              n_shards=self.n_shards,
                              slack=cfg.partition_slack,
                              with_bounds=self.use_kernel),
            self._specs["gather"],
            (pa, pa, pa, pa, pa, pa, P(dspec, axis, None), b1))
        self._allgather = smap(
            functools.partial(_sh_allgather, axis=axis),
            self._specs["allgather"], (b2, b2))
        self._stage2 = smap(
            functools.partial(_sh_stage2, n_docs=self.n_docs,
                              **self._stat),
            self._specs["stage2"], P(dspec, axis))
        self._rerank = smap(
            functools.partial(_sh_rerank, depth=cfg.rerank_depth,
                              **self._stat),
            self._specs["rerank"], b2)
        self._rerank_dyn = smap(
            functools.partial(_sh_rerank_dyn, depth=cfg.rerank_depth,
                              **self._stat),
            self._specs["rerank_dyn"], b2)

    # ----------------------------------------------- continuous serving --
    @property
    def supports_continuous(self) -> bool:
        """The sharded continuous scheduler keeps one slot-table replica:
        a data-parallel mesh would shard the slot rows over queries and
        the host-side slot bookkeeping does not span dp groups."""
        return self.dp_size == 1

    @property
    def continuous_unsupported_reason(self) -> str | None:
        if self.supports_continuous:
            return None
        return (f"the mesh has data-parallel axes {self.dp} (dp_size="
                f"{self.dp_size}); the sharded continuous scheduler "
                "needs a model-only mesh — use ShardedEngineBackend's "
                "batch-once path for data-parallel serving")

    def _stage1_for(self, pool_width: int):
        """Local stage 1 (no collective): per-shard survivors at
        kl = min(pool depth, shard_width)."""
        if self.cfg.knob == "rho":
            kl = min(self.cfg.rerank_depth, self.shard_width)
            return ("stage1", self._smap(functools.partial(
                _sh_stage1_local, knob="rho", kl=kl,
                route=self.topk_route(kl), **self._s1_stat),
                self._specs["stage1"],
                (P(self._specs["stage1"][0][0], self.axis),) * 2))
        kl = min(pool_width, self.shard_width)
        name = ("stage1" if pool_width == self.max_k
                else f"stage1:{pool_width}")
        return (name, self._smap(functools.partial(
            _sh_stage1_local, knob="k", kl=kl,
            route=self.topk_route(kl), **self._s1_stat),
            self._specs["stage1"],
            (P(self._specs["stage1"][0][0], self.axis),) * 2))

    def _merge_for(self, pool_width: int):
        """The lexsort half of the pool merge (runs after the all-gather
        has been overlapped with stage 2)."""
        dspec = self._specs["merge"][0][0]
        b2 = P(dspec, None)
        if self.cfg.knob == "rho":
            return ("merge", self._smap(functools.partial(
                _sh_merge_rho, depth=self.cfg.rerank_depth),
                self._specs["merge"][:2], b2))
        name = ("merge" if pool_width == self.max_k
                else f"merge:{pool_width}")
        return (name, self._smap(functools.partial(
            _sh_merge_k, max_k=pool_width),
            self._specs["merge"], b2))

    def _place(self, name: str, j: int, x):
        # commit each stage input to its mesh sharding before the AOT
        # lookup, so lowering and every later call see identical layouts
        # and the serving path never reshards
        spec = self._specs[name.split(":")[0]][j]
        return jax.device_put(x, NamedSharding(self.mesh, spec))

    def serve(self, query_terms: np.ndarray, param_vec: np.ndarray,
              pool_width: int | None = None,
              depth_vec: np.ndarray | None = None):
        """Overlapped sharded pipeline: gather(+partition) → local
        stage 1 → issue the survivor all-gather → dispatch stage 2 while
        the collective is in flight → lexsort-merge the pool → rerank.

        ``depth_vec`` follows the base engine's contract: None keeps the
        depth-free rerank, a vector dispatches ``rerank_dyn`` (the
        replicated pool masked before the pmax score assembly), and
        depth==pool-width is bit-identical to None.

        Timings: ``stage1_ms`` covers the local stage (dispatch to
        blocked); ``stage2_ms`` covers stage 2 *including* whatever part
        of the all-gather it hid; ``merge_ms`` is the residual merge
        latency after stage 2 landed.
        """
        n, qlen = query_terms.shape
        qt = bucketing.pad_rows(np.asarray(query_terms, np.int32),
                                self.batch_multiple, fill=-1)
        pv = bucketing.pad_rows(np.asarray(param_vec, np.int32),
                                self.batch_multiple, fill=1)
        if depth_vec is not None:
            depth_vec = bucketing.pad_rows(
                np.asarray(depth_vec, np.int32), self.batch_multiple,
                fill=1)
        qids = np.arange(qt.shape[0], dtype=np.int32)

        timings = {}

        def prep(name, fn, *a):
            a = tuple(self._place(name, j, jnp.asarray(x))
                      for j, x in enumerate(a))
            return self._compiled(name, fn, a), a

        def timed(label, name, fn, *a):
            exe, a = prep(name, fn, *a)
            self._m_dispatch.inc()
            with self.trace.span("engine." + name) as sp:
                out = exe(*a)
                jax.block_until_ready(out)
            timings[label] = sp.dur_ms
            return out

        width = int(pool_width or self.max_k)
        s1_name, s1_fn = self._stage1_for(width)
        ds_l, im_l, seg_lo, seg_hi, gpos, sd_l, s3_l, over = timed(
            "gather_ms", "gather", self._gather,
            self.offsets, self.pdoc, self.pimp, self.pscore, qt)
        v, gi = timed("stage1_ms", s1_name, s1_fn, ds_l, im_l, seg_lo,
                      seg_hi, gpos, pv)
        # issue the cross-shard survivor all-gather, then dispatch stage 2
        # while it is in flight; the merge consumes the gathered pool last
        ag_exe, ag_args = prep("allgather", self._allgather, v, gi)
        self._m_dispatch.inc()
        ag_out = ag_exe(*ag_args)
        m_name, m_fn = self._merge_for(width)
        s2_exe, s2_args = prep("stage2", self._stage2,
                               sd_l, s3_l, self.doc_len, qids)
        self._m_dispatch.inc()
        # the overlap seam: sync stage-2 FIRST, the gathered pool second
        # (see docs/INVARIANTS.md §4) — the spans wrap the existing sync
        # points without reordering them
        with self.trace.span("engine.stage2") as sp:
            stage2 = s2_exe(*s2_args)
            jax.block_until_ready(stage2)
        timings["stage2_ms"] = sp.dur_ms
        if self.cfg.knob == "rho":
            m_exe, m_args = prep(m_name, m_fn, *ag_out)
        else:
            m_exe, m_args = prep(m_name, m_fn, *ag_out, pv)
        self._m_dispatch.inc()
        with self.trace.span("engine.merge") as sp:
            pool = m_exe(*m_args)
            jax.block_until_ready(pool)
        timings["merge_ms"] = sp.dur_ms
        if depth_vec is None:
            ranked = timed("rerank_ms", "rerank", self._rerank, stage2,
                           pool)
        else:
            ranked = timed("rerank_ms", "rerank_dyn", self._rerank_dyn,
                           stage2, pool, depth_vec)
        ovf = int(np.asarray(over).max())
        if ovf > 0:
            raise RuntimeError(
                f"partition overflow: a shard owned {ovf} more postings "
                f"than its stream slot (shard_cap={self.shard_cap}, "
                f"stream_cap={self.cfg.stream_cap}, n_shards="
                f"{self.n_shards}); raise ServingConfig.partition_slack")
        ranked = _pad_ranked(np.asarray(ranked)[:n], self.cfg.rerank_depth)
        return ranked, timings


# ------------------------------------------------- scheduler programs --

@dataclasses.dataclass(frozen=True)
class SchedState:
    """The slot table's device residency: per-slot posting/score streams,
    segment bounds, and the resumable stage-1 accumulator.  Treated as an
    immutable value — every program returns a new state, so a failed
    dispatch can never leave half-updated rows behind."""

    ds: jax.Array        # (S, P) int32 posting doc ids, -1 padded
    im: jax.Array        # (S, P) float32 impacts, -1 padded
    seg_lo: jax.Array    # (S, n_blocks) int32 per-block min doc id
    seg_hi: jax.Array    # (S, n_blocks) int32 per-block max doc id
    sdocs: jax.Array     # (S, L*P) int32 stage-2 score-stream doc ids
    s3: jax.Array        # (S, L*P, 3) float32 stage-2 scorer features
    acc: jax.Array       # (S, n_docs) float32 resumable stage-1 scores
    # sharded programs only: per-posting global stream position of the
    # partitioned local streams (the rho bookkeeping), sentinel-padded
    gpos: jax.Array | None = None


def _default_chunk_p(p: int) -> int:
    """Largest divisor of the stream cap that is <= cap/8 — enough chunk
    positions for early retirement to matter, without a degenerate grid."""
    c = max(p // 8, 1)
    while p % c:
        c -= 1
    return c


class SchedPrograms:
    """The continuous scheduler's execution surface over ``ServingEngine``.

    Four programs — ``sgather``, ``refill``, ``chunk``, ``finalize`` —
    cover the whole slot lifecycle, and their shapes are fixed at
    construction (group width = the scheduler's refill grain, chunk span =
    the full slot table), so *any* admit/retire churn pattern reuses the
    same four AOT executables: the O(1)-compiles invariant survives the
    move from batch-once to continuous batching.  Per-slot stream
    positions and remaining budgets are traced operands; the host keeps
    the only authoritative copy, so no program ever reads device state
    back mid-flight (the d2h points are the admission-time stream length
    and the finalize result — the same vetted boundaries as ``serve``).

    ``ShardedSchedPrograms`` is the mesh variant over partitioned
    streams; construct through ``for_engine`` to get the right one (a
    sharded engine passed to this base class is refused — the base slot
    table assumes unsharded stage buffers).
    """

    #: host-visible flag the scheduler branches on: sharded programs
    #: advance per-slot *local* stream cursors (lpos/lend), base programs
    #: the global ones (pos/end)
    sharded = False

    @classmethod
    def for_engine(cls, engine: ServingEngine, *, grain: int,
                   chunk_p: int | None = None, extra_widths=()):
        """Construct the program set matching the engine's layout."""
        if isinstance(engine, ShardedServingEngine):
            return ShardedSchedPrograms(engine, grain=grain,
                                        chunk_p=chunk_p,
                                        extra_widths=extra_widths)
        return SchedPrograms(engine, grain=grain, chunk_p=chunk_p)

    def _slot_cap(self, engine: ServingEngine) -> int:
        """Per-slot posting-stream width the chunk geometry tiles (the
        sharded programs chunk the partitioned local streams)."""
        return engine.cfg.stream_cap

    def __init__(self, engine: ServingEngine, *, grain: int,
                 chunk_p: int | None = None):
        if (isinstance(engine, ShardedServingEngine)
                and not isinstance(self, ShardedSchedPrograms)):
            raise TypeError(
                "SchedPrograms' base slot table assumes unsharded stage "
                "buffers; build via SchedPrograms.for_engine (or "
                "ShardedSchedPrograms) for a mesh engine")
        self.engine = engine
        cfg = engine.cfg
        p = self._slot_cap(engine)
        self.grain = int(grain)
        self.slot_cap = p
        self.chunk_p = int(chunk_p) if chunk_p else _default_chunk_p(p)
        if p % self.chunk_p:
            raise ValueError(
                f"chunk_p={self.chunk_p} must divide the per-slot stream "
                f"width {p} so chunk windows tile the posting streams "
                "exactly")
        # segment bounds live at the coarsest granularity that still tiles
        # the chunk window, so a chunk's bounds are a contiguous gather
        self.bounds_p = (engine.block_p
                         if self.chunk_p % engine.block_p == 0
                         else self.chunk_p)
        self.n_chunks = p // self.chunk_p
        self._build_programs()

    def _build_programs(self):
        engine, cfg = self.engine, self.engine.cfg
        self._gather_fn = functools.partial(
            _sched_gather, cap=cfg.stream_cap, bounds_p=self.bounds_p,
            n_docs=engine.n_docs, with_bounds=engine.use_kernel)
        self._chunk_fn = functools.partial(
            _sched_chunk, chunk_p=self.chunk_p, bounds_p=self.bounds_p,
            n_docs=engine.n_docs, use_kernel=engine.use_kernel,
            interpret=engine.interpret, block_d=engine.block_d)
        route = engine.topk_route(cfg.depth_pool_width)
        engine.topk_routes["finalize"] = route
        common = dict(depth=cfg.rerank_depth, n_docs=engine.n_docs,
                      cap=cfg.stream_cap, route=route,
                      interpret=engine.interpret)
        if cfg.knob == "rho":
            self._final_fn = functools.partial(_sched_finalize_rho,
                                               **common)
        else:
            self._final_fn = functools.partial(_sched_finalize_k,
                                               max_k=engine.max_k,
                                               **common)

    def _run(self, name: str, fn, *args):
        a = tuple(jnp.asarray(x) for x in args)
        exe = self.engine._compiled(name, fn, a, scope="sched")
        self.engine._m_dispatch.inc()
        # the span covers the *dispatch window* only (no added sync —
        # chunk advances stay async; gather/finalize sync in the caller)
        with self.engine.trace.span("sched." + name):
            return exe(*a)

    def init_state(self, slots: int, query_len: int) -> SchedState:
        """Fresh (empty) slot table residency.  Segment bounds start at
        the empty interval (n_docs, -1) so unoccupied slots are never
        executed by the kernel grid."""
        e = self.engine
        p = e.cfg.stream_cap
        nb = p // self.bounds_p if e.use_kernel else 1
        lp = query_len * p
        return SchedState(
            ds=jnp.full((slots, p), -1, jnp.int32),
            im=jnp.full((slots, p), -1.0, jnp.float32),
            seg_lo=jnp.full((slots, nb), e.n_docs, jnp.int32),
            seg_hi=jnp.full((slots, nb), -1, jnp.int32),
            sdocs=jnp.full((slots, lp), -1, jnp.int32),
            s3=jnp.zeros((slots, lp, 3), jnp.float32),
            acc=jnp.zeros((slots, e.n_docs), jnp.float32),
        )

    def gather(self, qt: np.ndarray):
        """Gather one refill group's slot rows.  qt: (grain, L) int32,
        -1 padded.  Returns (device row tuple, host stream lengths,
        host local-end matrix — None here; the sharded programs fill it
        with per-candidate-width local stream ends)."""
        e = self.engine
        *rows, slen = self._run("sgather", self._gather_fn, e.offsets,
                                e.pdoc, e.pimp, e.pscore, qt)
        with e.trace.span("sched.sync", prog="sgather"):
            slen = np.asarray(slen)
        return tuple(rows), slen, None

    def refill(self, state: SchedState, slot_idx: np.ndarray,
               rows) -> SchedState:
        """Install gathered rows at ``slot_idx`` (pad entries == table
        capacity are dropped) and zero their accumulator rows."""
        out = self._run("refill", _sched_refill, state.ds, state.im,
                        state.seg_lo, state.seg_hi, state.sdocs, state.s3,
                        state.acc, slot_idx, *rows)
        return SchedState(*out)

    def chunk(self, state: SchedState, pos: np.ndarray,
              end: np.ndarray) -> SchedState:
        """Advance every active slot by one chunk window."""
        acc = self._run("chunk", self._chunk_fn, state.ds, state.im,
                        state.seg_lo, state.seg_hi, state.acc, pos, end)
        return dataclasses.replace(state, acc=acc)

    def _finalize_table(self, state: SchedState) -> tuple:
        """The slot-table buffers finalize reads: the accumulators, the
        doc streams its pool select reads them at, the score streams."""
        return state.acc, state.ds, state.sdocs, state.s3

    def finalize(self, state: SchedState, slot_idx: np.ndarray,
                 pvec: np.ndarray, dvec: np.ndarray,
                 qids: np.ndarray) -> np.ndarray:
        """Stages 1b-3 for a retiring group; returns host ranked lists
        (grain, rerank_depth).  ``pvec`` is the traced pool-width vector
        (k knob; ignored for rho, where the budget was applied in-chunk);
        ``dvec`` the traced per-slot reranking depth (the scheduler fills
        the static pool width when no depth knob is live — a no-op mask,
        bit-identical to the depth-free program)."""
        e = self.engine
        table = self._finalize_table(state)
        if e.cfg.knob == "rho":
            r = self._run("finalize", self._final_fn, *table, slot_idx,
                          dvec, qids, e.doc_len)
        else:
            r = self._run("finalize", self._final_fn, *table, slot_idx,
                          pvec, dvec, qids, e.doc_len)
        with e.trace.span("sched.sync", prog="finalize"):
            r = np.asarray(r)
        return _pad_ranked(r, e.cfg.rerank_depth)

    def warmup(self, slots: int, query_len: int) -> int:
        """Compile all four programs.  Safe mid-flight: the dummy refill
        scatters to all-out-of-bounds slot indices (every row dropped) and
        the dummy chunk runs at rho 0 (adds exact zeros), so live state is
        never perturbed.  Returns executables compiled."""
        e = self.engine
        with e._cache_lock:
            before = e.n_compiles
        g = self.grain
        state = self.init_state(slots, query_len)
        qt = np.full((g, query_len), -1, np.int32)
        rows, _, _ = self.gather(qt)
        state = self.refill(state, np.full(g, slots, np.int32), rows)
        zeros = np.zeros(slots, np.int32)
        state = self.chunk(state, zeros, zeros)
        self.finalize(state, np.zeros(g, np.int32),
                      np.ones(g, np.int32), np.ones(g, np.int32),
                      np.zeros(g, np.int32))
        with e._cache_lock:
            return e.n_compiles - before


# --------------------------------------- sharded scheduler stage bodies --
# shard_map bodies of ``ShardedSchedPrograms``: the continuous-batching
# slot table over doc-range-partitioned streams.  Each slot's posting
# stream is the ``shard_cap``-wide compacted local stream from
# ``partition_postings``; chunk windows advance a *local* cursor per
# shard, and the global rho budget applies through the stored global
# stream positions (``gpos``) exactly as in the batch-once sharded path.

def _ssched_gather(offsets, pdoc, pimp, pscore, qt, *, cap: int,
                   shard_cap: int, bounds_p: int, width: int, axis: str,
                   n_shards: int, slack: float, with_bounds: bool,
                   widths: tuple):
    """Per-request slot rows, partitioned, plus the host metadata row.

    The host schedules per-slot *local* cursors but cannot see per-shard
    stream lengths without a transfer, so this program folds everything
    it needs into one replicated ``meta`` matrix (a single d2h):
    column 0 the global stream length, column 1 the partition overflow
    (max over shards; the host raises on nonzero), columns 2.. the
    worst-shard local stream end ``max_s count(gpos_s < min(w, slen))``
    for every static candidate budget ``w`` in ``widths`` — the retire
    bound for whichever budget admission later picks."""
    lo = jax.lax.axis_index(axis) * width
    ds, im = jass.gather_streams(offsets, pdoc, pimp, qt, cap=cap)
    slen = jnp.sum(ds >= 0, axis=-1).astype(jnp.int32)
    ds_l, im_l, gpos, novf = partition_postings(ds, im, lo, width=width,
                                                cap=shard_cap)
    if with_bounds:
        seg_lo, seg_hi = block_doc_bounds(ds_l, block_p=bounds_p,
                                          n_docs=width)
    else:
        seg_lo = seg_hi = jnp.zeros((qt.shape[0], 1), jnp.int32)
    sdocs, s3 = jass.gather_score_streams(offsets, pdoc, pscore, qt,
                                          cap=cap)
    score_cap = partition_cap(sdocs.shape[-1], n_shards, slack)
    sd_l, s3_l, sovf = partition_scored_postings(sdocs, s3, lo,
                                                 width=width,
                                                 cap=score_cap)
    wvec = jnp.asarray(widths, jnp.int32)               # (W,) static grid
    endw = jnp.minimum(wvec[None, :], slen[:, None])    # (G, W)
    lend = jnp.sum(gpos[:, None, :] < endw[:, :, None],
                   axis=-1).astype(jnp.int32)
    lmax = jax.lax.pmax(lend, axis)
    ovf = jax.lax.pmax(jnp.maximum(novf, sovf), axis)
    meta = jnp.concatenate([slen[:, None], ovf[:, None], lmax], axis=1)
    return ds_l, im_l, seg_lo, seg_hi, gpos, sd_l, s3_l, meta


def _ssched_refill(ds_b, im_b, lo_b, hi_b, gp_b, sd_b, s3_b, acc,
                   slot_idx, ds, im, lo, hi, gp, sd, s3):
    """``_sched_refill`` plus the gpos buffer (8 buffers)."""
    drop = dict(mode="drop")
    return (ds_b.at[slot_idx].set(ds, **drop),
            im_b.at[slot_idx].set(im, **drop),
            lo_b.at[slot_idx].set(lo, **drop),
            hi_b.at[slot_idx].set(hi, **drop),
            gp_b.at[slot_idx].set(gp, **drop),
            sd_b.at[slot_idx].set(sd, **drop),
            s3_b.at[slot_idx].set(s3, **drop),
            acc.at[slot_idx].set(0.0, **drop))


def _ssched_chunk(ds_b, im_b, lo_b, hi_b, gp_b, acc, pos, end, *,
                  chunk_p: int, bounds_p: int, width: int,
                  use_kernel: bool, interpret: bool, block_d: int):
    """One resumable stage-1 step over the partitioned slot table.

    ``pos`` is the per-slot *local* chunk cursor (multiples of
    ``chunk_p``; the host advances it to the worst-shard local end),
    ``end`` the per-slot *global* rho budget.  The window's admitted
    postings are those with ``gpos < end`` — a prefix of the window,
    since gpos is increasing along the compacted stream — so the count
    is a drop-in window rho for the same masked accumulate as the base
    program.  A shard whose local stream ended before ``pos`` sees
    count 0 and adds exact zeros, so slots retire at the worst shard's
    end without per-shard host bookkeeping."""
    lc = ds_b.shape[-1]
    off = pos[:, None] + jnp.arange(chunk_p, dtype=jnp.int32)[None, :]
    idx = jnp.minimum(off, lc - 1)      # dead clamp: pos < lend <= lc
    ds = jnp.take_along_axis(ds_b, idx, axis=1)
    im = jnp.take_along_axis(im_b, idx, axis=1)
    gp = jnp.take_along_axis(gp_b, idx, axis=1)
    rho_rem = jnp.sum(gp < end[:, None], axis=-1).astype(jnp.int32)
    if use_kernel:
        nb = chunk_p // bounds_p
        bidx = (pos[:, None] // bounds_p
                + jnp.arange(nb, dtype=jnp.int32)[None, :])
        bidx = jnp.minimum(bidx, lo_b.shape[-1] - 1)
        seg = (jnp.take_along_axis(lo_b, bidx, axis=1),
               jnp.take_along_axis(hi_b, bidx, axis=1))
    else:
        seg = None
    inc = jass.saat_scores_masked(ds, im, rho_rem, width,
                                  use_kernel=use_kernel,
                                  interpret=interpret, seg_bounds=seg,
                                  block_p=bounds_p, block_d=block_d)
    return acc + inc


def _ssched_finalize_rho(acc, sd_b, s3_b, slot_idx, dvec, qids, doc_len,
                         *, depth: int, axis: str, width: int,
                         n_docs: int, route: str, interpret: bool):
    """Sharded stages 1b-3 for a retiring group: cross-shard pool merge
    over the finished local accumulator rows, partitioned stage 2,
    pmax-assembled rerank — the batch-once sharded tail on slot rows.
    ``dvec`` is the traced per-slot reranking depth (static pool width
    when no depth knob is live — a no-op mask)."""
    rows = acc[slot_idx]
    pool = _pool_from_local(rows, depth, axis=axis, width=width,
                            route=route, interpret=interpret)
    stage2 = _sh_stage2(sd_b[slot_idx], s3_b[slot_idx], doc_len, qids,
                        axis=axis, width=width, n_docs=n_docs)
    return _sh_rerank(stage2, _depth_mask(pool, dvec), axis=axis,
                      width=width, depth=depth)


def _ssched_finalize_k(acc, sd_b, s3_b, slot_idx, k_vec, dvec, qids,
                       doc_len, *, depth: int, max_k: int, axis: str,
                       width: int, n_docs: int, route: str,
                       interpret: bool):
    rows = acc[slot_idx]
    pool = _pool_from_local(rows, max_k, axis=axis, width=width,
                            route=route, interpret=interpret)
    keep = jnp.arange(pool.shape[-1])[None, :] < k_vec[:, None]
    pool = jnp.where(keep, pool, -1)
    stage2 = _sh_stage2(sd_b[slot_idx], s3_b[slot_idx], doc_len, qids,
                        axis=axis, width=width, n_docs=n_docs)
    return _sh_rerank(stage2, _depth_mask(pool, dvec), axis=axis,
                      width=width, depth=depth)


class ShardedSchedPrograms(SchedPrograms):
    """``SchedPrograms`` over a ``ShardedServingEngine``'s partitioned
    streams: the same four fixed-shape programs, with chunk windows that
    advance per-shard over the ``shard_cap``-wide local streams.

    Chunk geometry derives from ``shard_cap`` (not the global
    ``stream_cap``), so a chunk step reads ~1/n_shards of the postings a
    replicated layout would.  Zero-compiles-under-churn carries over
    unchanged: every program's shapes are fixed at construction, the
    candidate-budget grid (``widths``) is static, and per-slot cursors
    stay traced operands.  Retirement needs one extra host fact — the
    worst-shard local stream end for the slot's budget — which the
    gather program precomputes for every static budget and ships in the
    single ``meta`` d2h (no mid-flight readbacks).

    Bit-identity: each slot's accumulator rows receive exactly the
    batch-once sharded engine's additions (same partitioned streams,
    same window masks summing to the same per-posting admits), and
    finalize runs the batch-once sharded tail verbatim.
    """

    sharded = True

    def __init__(self, engine: ServingEngine, *, grain: int,
                 chunk_p: int | None = None, extra_widths=()):
        if not isinstance(engine, ShardedServingEngine):
            raise TypeError("ShardedSchedPrograms needs a "
                            "ShardedServingEngine; use SchedPrograms "
                            "(or for_engine) for the unsharded engine")
        if not engine.supports_continuous:
            raise TypeError("ShardedSchedPrograms: "
                            + engine.continuous_unsupported_reason)
        self._extra_widths = tuple(int(w) for w in extra_widths)
        super().__init__(engine, grain=grain, chunk_p=chunk_p)

    def _slot_cap(self, engine: ServingEngine) -> int:
        return engine.shard_cap

    def _finalize_table(self, state: SchedState) -> tuple:
        # each shard selects over its dense accumulator rows, then merges
        return state.acc, state.sdocs, state.s3

    def lend_col(self, width: int) -> int:
        """meta column (minus the 2-column prefix) of the local-end bound
        for a slot whose global budget is ``min(width, slen)``."""
        return self.width_col[min(int(width), self.engine.cfg.stream_cap)]

    def _build_programs(self):
        e, cfg = self.engine, self.engine.cfg
        cap = cfg.stream_cap
        # the static candidate-budget grid: every global end the
        # scheduler can assign is min(w, slen) for one of these w —
        # cutoff widths (rho knob), the full cap (k knob / stream
        # exhaustion), and any fixed-sweep extras
        ws = {min(int(c), cap) for c in cfg.cutoffs} | {cap}
        ws |= {min(int(w), cap) for w in self._extra_widths}
        self.widths = tuple(sorted(ws))
        self.width_col = {w: i for i, w in enumerate(self.widths)}

        axis, width = e.axis, e.shard_width
        ss, ss3 = P(None, axis), P(None, axis, None)
        r1, r2, sacc = P(None), P(None, None), P(None, axis)
        #: per-program input PartitionSpecs — ``_run`` commits every host
        #: arg to these before the AOT lookup (the executables bake their
        #: input shardings at lowering)
        self._arg_specs = {
            "sgather": (P(None), P(None), P(None), P(None, None), r2),
            "refill": (ss, ss, ss, ss, ss, ss, ss3, sacc, r1,
                       ss, ss, ss, ss, ss, ss, ss3),
            "chunk": (ss, ss, ss, ss, ss, sacc, r1, r1),
            "finalize": ((sacc, ss, ss3, r1, r1, r1, P(axis))
                         if cfg.knob == "rho"
                         else (sacc, ss, ss3, r1, r1, r1, r1, P(axis))),
        }
        smap = e._smap
        self._gather_fn = smap(
            functools.partial(_ssched_gather, cap=cap,
                              shard_cap=e.shard_cap,
                              bounds_p=self.bounds_p, width=width,
                              axis=axis, n_shards=e.n_shards,
                              slack=cfg.partition_slack,
                              with_bounds=e.use_kernel,
                              widths=self.widths),
            self._arg_specs["sgather"],
            (ss, ss, ss, ss, ss, ss, ss3, r2))
        self._refill_fn = smap(_ssched_refill, self._arg_specs["refill"],
                               (ss, ss, ss, ss, ss, ss, ss3, sacc))
        self._chunk_fn = smap(
            functools.partial(_ssched_chunk, chunk_p=self.chunk_p,
                              bounds_p=self.bounds_p, width=width,
                              use_kernel=e.use_kernel,
                              interpret=e.interpret, block_d=e.block_d),
            self._arg_specs["chunk"], sacc)
        route = e.topk_route(min(cfg.depth_pool_width, width))
        e.topk_routes["finalize"] = route
        common = dict(depth=cfg.rerank_depth, axis=axis, width=width,
                      n_docs=e.n_docs, route=route, interpret=e.interpret)
        if cfg.knob == "rho":
            self._final_fn = smap(
                functools.partial(_ssched_finalize_rho, **common),
                self._arg_specs["finalize"], r2)
        else:
            self._final_fn = smap(
                functools.partial(_ssched_finalize_k, max_k=e.max_k,
                                  **common),
                self._arg_specs["finalize"], r2)

    def _run(self, name: str, fn, *args):
        # the AOT executables bake their input shardings at lowering, so
        # every arg — host scalars and device buffers alike — is
        # committed to its program spec first (a no-op for buffers
        # already placed by the previous program's out specs)
        mesh = self.engine.mesh
        a = tuple(jax.device_put(jnp.asarray(x), NamedSharding(mesh, s))
                  for x, s in zip(args, self._arg_specs[name]))
        exe = self.engine._compiled(name, fn, a, scope="sched")
        self.engine._m_dispatch.inc()
        with self.engine.trace.span("sched." + name):
            return exe(*a)

    def init_state(self, slots: int, query_len: int) -> SchedState:
        """Fresh slot table over the partitioned layout: every buffer is
        the *global* view of per-shard blocks (stream columns sharded
        over the mesh axis) and is committed to its program sharding up
        front.  gpos pads at the stream-cap sentinel (never < any
        budget), local segment bounds start at the local empty interval
        (shard_width, -1)."""
        e = self.engine
        s = e.n_shards
        lc = e.shard_cap
        nb = lc // self.bounds_p if e.use_kernel else 1
        lp = partition_cap(query_len * e.cfg.stream_cap, s,
                           e.cfg.partition_slack)
        mesh, axis = e.mesh, e.axis

        def put(x, spec):
            return jax.device_put(x, NamedSharding(mesh, spec))

        ss, ss3, sacc = P(None, axis), P(None, axis, None), P(None, axis)
        return SchedState(
            ds=put(np.full((slots, s * lc), -1, np.int32), ss),
            im=put(np.full((slots, s * lc), -1.0, np.float32), ss),
            seg_lo=put(np.full((slots, s * nb), e.shard_width, np.int32),
                       ss),
            seg_hi=put(np.full((slots, s * nb), -1, np.int32), ss),
            sdocs=put(np.full((slots, s * lp), -1, np.int32), ss),
            s3=put(np.zeros((slots, s * lp, 3), np.float32), ss3),
            acc=put(np.zeros((slots, e.doc_pad), np.float32), sacc),
            gpos=put(np.full((slots, s * lc), e.cfg.stream_cap,
                             np.int32), ss),
        )

    def gather(self, qt: np.ndarray):
        """Partitioned slot rows + the single-d2h host metadata: returns
        (rows, global stream lengths, (G, W) local-end matrix indexed by
        ``lend_col``).  Raises on partition overflow."""
        e = self.engine
        *rows, meta = self._run("sgather", self._gather_fn, e.offsets,
                                e.pdoc, e.pimp, e.pscore, qt)
        with e.trace.span("sched.sync", prog="sgather"):
            m = np.asarray(meta)
        slen, ovf, lend = m[:, 0], m[:, 1], m[:, 2:]
        worst = int(ovf.max()) if ovf.size else 0
        if worst > 0:
            raise RuntimeError(
                f"partition overflow: a shard owned {worst} more "
                f"postings than its stream slot (shard_cap={e.shard_cap},"
                f" stream_cap={e.cfg.stream_cap}, n_shards={e.n_shards});"
                " raise ServingConfig.partition_slack")
        return tuple(rows), slen, lend

    def refill(self, state: SchedState, slot_idx: np.ndarray,
               rows) -> SchedState:
        out = self._run("refill", self._refill_fn, state.ds, state.im,
                        state.seg_lo, state.seg_hi, state.gpos,
                        state.sdocs, state.s3, state.acc, slot_idx,
                        *rows)
        ds, im, lo, hi, gp, sd, s3, acc = out
        return SchedState(ds=ds, im=im, seg_lo=lo, seg_hi=hi, sdocs=sd,
                          s3=s3, acc=acc, gpos=gp)

    def chunk(self, state: SchedState, pos: np.ndarray,
              end: np.ndarray) -> SchedState:
        """Advance every active slot by one *local* chunk window (``pos``
        is the local cursor; ``end`` stays the global rho budget)."""
        acc = self._run("chunk", self._chunk_fn, state.ds, state.im,
                        state.seg_lo, state.seg_hi, state.gpos,
                        state.acc, pos, end)
        return dataclasses.replace(state, acc=acc)
