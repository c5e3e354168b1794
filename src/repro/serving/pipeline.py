"""The multi-stage retrieval pipeline with dynamic trade-off prediction.

End-to-end serving path (paper Figure 1 + our cascade in front):

    query -> static features (core.features, precomputed term stats)
          -> LR cascade -> predicted class (a k or rho bucket)
          -> single-dispatch candidate generation (traced per-query k/rho)
          -> feature extraction (per-candidate stage-2 features)
          -> second-stage reranker -> final ranked list

Everything after the class prediction runs through the batch-once
single-dispatch engine (serving/engine.py): streams and stage-2
accumulators are gathered once per batch, and the predicted parameter is
a traced vector, so the executable count is constant regardless of how
many distinct classes the cascade predicts.  ``serve_batch_reference``
keeps the original per-bucket execution model for equivalence testing.

``serve_batch`` returns the latency accounting the paper's efficiency
claims are stated in: postings scored (rho semantics), candidate-pool
width (k semantics — the rerank cost driver), and per-stage wall-clock.
"""

from __future__ import annotations

import dataclasses
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import cascade as cascade_lib
from repro.core import features as feat_lib
from repro.core import forest as forest_lib
from repro.core import knobs as knobs_lib
from repro.retrieval import gold, jass
from repro.serving import bucketing
from repro.serving.engine import (ServingEngine, ShardedServingEngine,
                                  named)

__all__ = ["ServingConfig", "RetrievalServer"]


@dataclasses.dataclass
class ServingConfig:
    knob: str                      # "k" | "rho"
    cutoffs: tuple[int, ...]       # the 9 parameter values
    threshold: float = 0.75        # cascade confidence t
    rerank_depth: int = 100        # final list depth
    stream_cap: int = 4096         # postings stream length P
    pad_multiple: int = 8
    use_kernel: bool | None = None  # None: Pallas on TPU (or
    #                               REPRO_FORCE_KERNEL=1), jnp oracle else
    kernel_block_p: int = 512       # impact_scan posting-block size
    kernel_block_d: int = 2048     # impact_scan doc-tile size
    partition_slack: float = 2.0    # per-shard stream headroom multiplier
    #                               (sharded engine: shard stream cap =
    #                               ~slack * cap / n_shards, overflow is
    #                               detected and raised loudly)
    depth_cutoffs: tuple[int, ...] | None = None  # reranking-depth grid
    #                               (third knob); None = depth knob off.
    #                               Must end at depth_pool_width so the
    #                               top class masks nothing.

    def __post_init__(self):
        if self.knob not in ("rho", "k"):
            raise ValueError(f"knob must be 'rho' or 'k', got "
                             f"{self.knob!r}")
        knobs_lib.KnobSpec(self.knob, tuple(self.cutoffs))  # grid checks
        if self.knob == "k" and self.rerank_depth > max(self.cutoffs):
            # the engine pads the ranked list with the explicit -1
            # sentinel when the pool is narrower than rerank_depth;
            # under the k knob *every* query's pool is at most
            # max(cutoffs) wide, so such a config silently pads every
            # row — reject it at construction instead
            raise ValueError(
                f"rerank_depth={self.rerank_depth} exceeds the widest "
                f"candidate pool max(cutoffs)={max(self.cutoffs)}: every "
                "ranked list would be -1-padded past the pool width")
        if self.depth_cutoffs is not None:
            spec = knobs_lib.KnobSpec("depth", tuple(self.depth_cutoffs))
            if spec.reference() != self.depth_pool_width:
                raise ValueError(
                    f"depth grid must end at the candidate-pool width "
                    f"{self.depth_pool_width} (its reference: masking at "
                    f"it is a no-op), got max {spec.reference()}")

    @property
    def depth_pool_width(self) -> int:
        """Static width of the candidate pool the depth knob masks: the
        rerank pool is ``rerank_depth`` wide under rho (stage 1 ranks
        the top rerank_depth) and ``max(cutoffs)`` wide under k (the
        shared pool is sized to the widest cutoff)."""
        return (self.rerank_depth if self.knob == "rho"
                else max(self.cutoffs))


class RetrievalServer:
    """Owns the index-derived arrays + trained cascade; serves batches."""

    def __init__(self, index, casc: cascade_lib.Cascade,
                 cfg: ServingConfig, *,
                 depth_cascade: cascade_lib.Cascade | None = None,
                 mesh=None, shard_axis: str = "model",
                 warmup_batch_sizes: tuple[int, ...] = (),
                 warmup_query_len: int = 0):
        self.index = index
        self.cascade = casc
        self.depth_cascade = depth_cascade
        self.cfg = cfg
        # the knob registry: every per-query knob this server drives,
        # each a named cutoff grid sharing the same cascade machinery
        # (core.knobs).  The primary knob (cfg.knob) parameterizes
        # stage 1; the optional "depth" knob bounds the scored prefix
        # of the stage-2 candidate pool.
        self.knobs = {cfg.knob: knobs_lib.KnobSpec(cfg.knob,
                                                   tuple(cfg.cutoffs))}
        if cfg.depth_cutoffs is not None:
            self.knobs["depth"] = knobs_lib.KnobSpec(
                "depth", tuple(cfg.depth_cutoffs))
        elif depth_cascade is not None:
            raise ValueError(
                "depth_cascade given but cfg.depth_cutoffs is None — "
                "declare the depth grid in ServingConfig")
        self.stats = jnp.asarray(index.term_stats.stats)
        self.ctf = jnp.asarray(index.term_stats.ctf)
        self.df = jnp.asarray(index.term_stats.df)
        self.n_docs = index.corpus.n_docs
        # the engine owns the device copies of the postings arrays; the
        # reference path reads them from there (they dominate memory).
        # With a mesh, the candidate universe shards over `shard_axis`
        # and request batches over the data axes — same serve() surface,
        # bit-identical output.
        if mesh is not None:
            self.engine = ShardedServingEngine(
                index, cfg, mesh, axis=shard_axis,
                use_kernel=cfg.use_kernel)
        else:
            self.engine = ServingEngine(index, cfg,
                                        use_kernel=cfg.use_kernel)
        # built eagerly (jax.jit is lazy until called) so concurrent
        # predict_classes callers — the service's admit + warmup threads —
        # never race a lazy init.  The cascade weights enter the jitted
        # executable as *runtime operands* (a pytree argument), never as
        # baked-in constants: the online adaptation loop hot-swaps
        # retrained weights of identical shapes into the live predict
        # path with a single reference assignment and zero recompiles.
        # Forest node tables are padded to the depth-derived capacity so
        # every same-depth retrain produces identically-shaped params.
        self._predict_fns = {}         # knob -> jitted predict
        self._margin_fns = {}          # knob -> jitted uncertainty margin
        self._live = {}                # knob -> (node_params, thresholds)
        self._swap_lock = threading.Lock()
        self.predictor_version = 0
        self.fallback = False          # drift monitor: serve static max
        if casc is not None:
            self._boot_knob(cfg.knob, casc)
        if depth_cascade is not None:
            self._boot_knob("depth", depth_cascade)
        if warmup_batch_sizes and warmup_query_len:
            self.engine.warmup(warmup_batch_sizes, warmup_query_len,
                               with_depth=self.has_depth_knob)
            for knob in self._predict_fns:  # pre-compile fused predicts
                for b in sorted({self.engine.padded_batch(int(x))
                                 for x in warmup_batch_sizes}):
                    self.predict_classes(
                        np.full((b, warmup_query_len), -1, np.int32),
                        knob=knob)

    def _boot_knob(self, knob: str, casc: cascade_lib.Cascade) -> None:
        """Install a knob's boot cascade: padded device params + jitted
        predict/margin executables.  Called from ``__init__`` only (the
        object is not yet shared), but takes the swap lock anyway so the
        lock contract holds by inspection."""
        if knob not in self.knobs:
            raise ValueError(f"no cutoff grid declared for knob {knob!r}")
        if casc.n_cutoffs != self.knobs[knob].n_cutoffs:
            raise ValueError(
                f"knob {knob!r}: cascade has {casc.n_cutoffs} nodes but "
                f"the grid has {self.knobs[knob].n_cutoffs} cutoffs")
        node_params = casc.node_params
        if casc.kind == "forest":
            cap = forest_lib.node_capacity(casc.max_depth)
            node_params = [forest_lib.pad_forest_params(p, cap)
                           for p in node_params]
        thresholds = jnp.full((casc.n_cutoffs,), self.cfg.threshold,
                              jnp.float32)
        # commit the boot params to device once, like swap_predictor
        # does: otherwise every predict_classes call re-uploads any
        # host-resident leaf — an implicit h2d transfer per batch
        # that jax.transfer_guard("disallow") rightly rejects
        node_params = jax.device_put(node_params)
        kind, depth = casc.kind, casc.max_depth

        # the per-term statistics tables are operands too: closed over,
        # they would be baked into every predict executable as constants
        # (hundreds of MB at a deployment's vocabulary)
        def _predict(node_params, thresholds, q, tables):
            x = feat_lib.query_features(q, *tables)
            p0 = cascade_lib.proba0_from_params(kind, node_params, x,
                                                depth)
            return cascade_lib.classes_from_proba(p0, thresholds)

        def _margin(node_params, thresholds, q, tables):
            x = feat_lib.query_features(q, *tables)
            p0 = cascade_lib.proba0_from_params(kind, node_params, x,
                                                depth)
            return jnp.min(jnp.abs(p0 - thresholds[None, :]), axis=1)

        # stable module names on the device trace: jit_cascade_<knob>,
        # jit_margin_<knob>
        self._predict_fns[knob] = jax.jit(named(_predict, f"cascade_{knob}"))
        self._margin_fns[knob] = jax.jit(named(_margin, f"margin_{knob}"))
        with self._swap_lock:
            self._live = {**self._live, knob: (node_params, thresholds)}

    @property
    def has_depth_knob(self) -> bool:
        """True when the config declares a reranking-depth grid — the
        serve path then always passes a traced depth vector (the
        reference depth until a depth cascade is installed)."""
        return "depth" in self.knobs

    # stage 0: prediction ------------------------------------------------
    def predict_classes(self, query_terms: np.ndarray,
                        knob: str | None = None) -> np.ndarray:
        """Featurize + cascade, fused into one jitted executable.

        Run eagerly the cascade is hundreds of small forest ops and
        dominates batch latency; jitted it is the negligible overhead the
        paper claims.  Queries are padded to the engine's batch grid
        (which a mesh-sharded engine widens to divide over the data axes)
        so the prediction executable count matches the engine's: one per
        padded shape.

        ``knob`` selects which registered knob's cascade runs (default:
        the primary ``cfg.knob``).  A declared knob with no cascade
        installed yet predicts the no-envelope class for every query —
        ``params_of`` maps that to the knob's reference (full fidelity),
        so e.g. a depth knob serves at full depth until its first
        trained cascade arrives."""
        knob = self.cfg.knob if knob is None else knob
        n = query_terms.shape[0]
        # one dict read: the swap path replaces the whole dict, so a
        # concurrent swap_predictor can never hand this call params from
        # one version and thresholds from another
        live = self._live
        if knob not in live:
            return np.full(n, self.knobs[knob].n_cutoffs, np.int32)
        qt = bucketing.pad_rows(np.asarray(query_terms, np.int32),
                                self.engine.batch_multiple, fill=-1)
        node_params, thresholds = live[knob]
        return np.asarray(self._predict_fns[knob](
            node_params, thresholds, jnp.asarray(qt),
            (self.stats, self.ctf, self.df)))[:n]

    def predict_margin(self, query_terms: np.ndarray,
                       knob: str | None = None) -> np.ndarray:
        """Per-query cascade uncertainty: min over nodes of the distance
        between the node's class-0 probability and its exit threshold.

        Small margin = the query sits near a cascade decision boundary —
        exactly the queries the shadow executor's importance sampler
        labels first.  Off the hot serve path, so it takes the swap lock
        for its snapshot rather than adding a second vetted lock-free
        ``_live`` read.  Knobs with no cascade installed report zero
        margin (maximally uncertain: nothing is known about them)."""
        knob = self.cfg.knob if knob is None else knob
        n = query_terms.shape[0]
        with self._swap_lock:
            live = self._live.get(knob)
        if live is None:
            return np.zeros(n, np.float32)
        qt = bucketing.pad_rows(np.asarray(query_terms, np.int32),
                                self.engine.batch_multiple, fill=-1)
        node_params, thresholds = live
        return np.asarray(self._margin_fns[knob](
            node_params, thresholds, jnp.asarray(qt),
            (self.stats, self.ctf, self.df)))[:n]

    def swap_predictor(self, node_params, thresholds=None, *,
                       version: int | None = None,
                       knob: str | None = None) -> int:
        """Atomically replace a knob's live cascade weights (and
        optionally its per-node thresholds) in the jitted predict path.

        The incoming pytree must match the live one in structure, shapes
        and dtypes — anything else would silently trigger a recompile, so
        it raises instead (``online.store.PredictorStore`` pads retrained
        forests to the shared capacity precisely to satisfy this).  The
        swap is one reference assignment of the whole per-knob dict:
        in-flight predictions finish on the snapshot they read, the next
        ``predict_classes`` sees the new one, and there is no window
        where params and thresholds mix versions.  The old version's
        device buffers are *not* deleted eagerly — concurrent predict
        threads (admit + warmup) may still be executing on them, which is
        also why the params are plain operands rather than jit-donated
        arguments; they are freed when the last in-flight call drops its
        reference."""
        knob = self.cfg.knob if knob is None else knob
        if knob not in self._predict_fns:
            raise RuntimeError(
                f"server has no cascade predict path for knob {knob!r} "
                "to swap (no boot cascade was installed for it)")
        with self._swap_lock:
            old_params, old_thr = self._live[knob]
            flat_new, tree_new = jax.tree_util.tree_flatten(node_params)
            flat_old, tree_old = jax.tree_util.tree_flatten(old_params)
            if tree_new != tree_old:
                raise ValueError(
                    "swapped predictor pytree structure differs from the "
                    f"live one ({tree_new} vs {tree_old}); this would "
                    "recompile the predict executable")
            for a, b in zip(flat_new, flat_old):
                if a.shape != b.shape or a.dtype != b.dtype:
                    raise ValueError(
                        "swapped predictor leaf mismatch: "
                        f"{a.shape}/{a.dtype} vs live {b.shape}/{b.dtype}"
                        " — pad retrained params to the template "
                        "(online.store.PredictorStore)")
            node_params = jax.device_put(node_params)
            if thresholds is None:
                thresholds = old_thr
            else:
                thresholds = jnp.asarray(thresholds, jnp.float32)
                if thresholds.shape != old_thr.shape:
                    raise ValueError(
                        f"thresholds shape {thresholds.shape} != live "
                        f"{old_thr.shape}")
                thresholds = jax.device_put(thresholds)
            self._live = {**self._live, knob: (node_params, thresholds)}
            self.predictor_version = (self.predictor_version + 1
                                      if version is None else int(version))
            return self.predictor_version

    def params_of(self, classes: np.ndarray,
                  knob: str | None = None) -> np.ndarray:
        """Predicted class -> engine parameter (k, rho, or depth) vector
        via the knob's registered grid (``core.knobs.KnobSpec``).

        When the drift monitor has tripped ``fallback``, every query is
        served at the knob's static reference (the global-baseline
        escape hatch) regardless of the predicted class."""
        knob = self.cfg.knob if knob is None else knob
        p = self.knobs[knob].params_of(classes, fallback=self.fallback)
        if knob == "rho":
            p = np.minimum(p, self.cfg.stream_cap)
        return p.astype(np.int64)

    _params_of = params_of            # pre-service-API spelling

    def predict_depths(self, query_terms: np.ndarray):
        """(depth classes, depth vector) for a batch, or (None, None)
        when the depth knob is off.  With no depth cascade installed the
        classes are all no-envelope -> the vector is the full pool width
        (a no-op mask, bit-identical to the depth-free path)."""
        if not self.has_depth_knob:
            return None, None
        dclasses = self.predict_classes(query_terms, knob="depth")
        return dclasses, self.params_of(dclasses, knob="depth")

    def _rows_scored(self, widths: np.ndarray, depths: np.ndarray):
        """Deterministic stage-2 work accounting under the depth knob:
        per-query candidate-pool rows admitted into the rerank
        (``min(depth, pool rows)``) vs the depth-free pool rows."""
        full = (widths if self.cfg.knob == "k"
                else np.full_like(widths, self.cfg.rerank_depth))
        return np.minimum(depths, full), full

    def serve_batch(self, query_terms: np.ndarray) -> dict:
        """Full dynamic pipeline over a query batch, single-dispatch."""
        t0 = time.perf_counter()
        classes = self.predict_classes(query_terms)
        dclasses, depths = self.predict_depths(query_terms)
        predict_ms = (time.perf_counter() - t0) * 1e3
        widths = self.params_of(classes)
        ranked, timings = self.engine.serve(query_terms, widths,
                                            depth_vec=depths)
        timings["predict_ms"] = predict_ms
        timings["total_ms"] = (time.perf_counter() - t0) * 1e3
        out = {
            "ranked": ranked,
            "classes": classes,
            "mean_param": float(widths.mean()),
            "widths": widths.astype(np.float64),
            "timings": timings,
            "n_compiles": self.engine.n_compiles,
        }
        if depths is not None:
            rows, full = self._rows_scored(widths, depths)
            out["depth_classes"] = dclasses
            out["depths"] = depths.astype(np.float64)
            out["stage2_rows_scored"] = int(rows.sum())
            out["stage2_rows_full"] = int(full.sum())
        return out

    def serve_fixed(self, query_terms: np.ndarray, param: int, *,
                    depth: int | None = None) -> dict:
        """Fixed-global-parameter baseline (the tradeoff horizon) — same
        engine, constant parameter vector, so it shares executables with
        the dynamic path.  ``depth`` optionally pins the reranking depth
        for every query (the shadow executor's per-cutoff depth re-runs);
        None keeps the depth-free rerank program."""
        t0 = time.perf_counter()
        n = query_terms.shape[0]
        pool_width = None
        if self.cfg.knob == "rho":
            param = min(param, self.cfg.stream_cap)
        elif param > self.engine.max_k:
            # wider than the shared pool: request a dedicated executable
            # at this width rather than silently truncating the pool
            pool_width = param
        widths = np.full(n, param, np.int64)
        dvec = (None if depth is None
                else np.full(n, int(depth), np.int64))
        ranked, timings = self.engine.serve(query_terms, widths,
                                            pool_width=pool_width,
                                            depth_vec=dvec)
        timings["predict_ms"] = 0.0
        timings["total_ms"] = (time.perf_counter() - t0) * 1e3
        return {"ranked": ranked, "mean_param": float(param),
                "widths": widths.astype(np.float64), "timings": timings,
                "n_compiles": self.engine.n_compiles}

    # ------------------------------------------- reference (per-bucket) --
    def _serve_bucket(self, query_terms: np.ndarray, param: int,
                      qids: np.ndarray):
        """Original per-bucket path: candidate generation + feature
        extraction + rerank at one static parameter setting.  Re-gathers
        streams and re-materializes the stage-2 accumulators per call —
        kept as the equivalence oracle for the engine."""
        qt = jnp.asarray(query_terms)
        eng = self.engine
        ds, im = jass.gather_streams(eng.offsets, eng.pdoc, eng.pimp,
                                     qt, cap=self.cfg.stream_cap)
        if self.cfg.knob == "rho":
            rho = min(param, self.cfg.stream_cap)
            acc = jass.saat_scores(ds, im, self.n_docs, rho)
            pool = jass.rank_from_scores(acc, self.cfg.rerank_depth)
            width = rho
        else:
            acc = jass.saat_scores(ds, im, self.n_docs, ds.shape[-1])
            pool = jass.rank_from_scores(acc, param)
            width = param
        sdocs, s3 = jass.gather_score_streams(
            eng.offsets, eng.pdoc, eng.pscore, qt,
            cap=self.cfg.stream_cap)
        a_bm25, a_lm, a_tfidf = jass.scorer_accumulators(
            sdocs, s3, self.n_docs)
        stage2 = gold.second_stage_scores(
            a_bm25, a_lm, a_tfidf,
            jnp.asarray(self.index.corpus.doc_len), jnp.asarray(qids))
        ranked = np.asarray(
            gold.rerank_pool(stage2, pool, self.cfg.rerank_depth))
        if ranked.shape[1] < self.cfg.rerank_depth:   # pool narrower than
            pad = self.cfg.rerank_depth - ranked.shape[1]  # the final list
            ranked = np.pad(ranked, ((0, 0), (0, pad)), constant_values=-1)
        return ranked, width

    def serve_batch_reference(self, query_terms: np.ndarray) -> dict:
        """Per-bucket execution model (one static-shape program per
        predicted class) — O(unique classes) dispatches and compiles."""
        n = query_terms.shape[0]
        classes = self.predict_classes(query_terms)
        buckets = bucketing.bucketize(classes, len(self.cfg.cutoffs),
                                      self.cfg.pad_multiple)
        results, widths = {}, np.zeros(n)
        for c, b in buckets.items():
            param = self.cfg.cutoffs[min(c, len(self.cfg.cutoffs) - 1)]
            ranked, width = self._serve_bucket(query_terms[b["pad_idx"]],
                                               int(param), b["pad_idx"])
            results[c] = ranked
            widths[b["idx"]] = width
        ranked_all = bucketing.scatter_back(n, buckets, results)
        return {
            "ranked": ranked_all,
            "classes": classes,
            "mean_param": float(widths.mean()),
            "widths": widths,
        }
