"""Chip smoke test: the served retrieval path, once, on a TPU, at the size
of a real deployment.

    python3 chip_smoke.py               # one chip
    python3 chip_smoke.py --four-chips  # the doc-range-sharded engine on
                                        # four chips, against one chip

The deployment is one chip's doc-range shard of MS MARCO passage ranking:
8,841,823 passages over four chips is 2,210,456 passages per chip, with a
mean passage length of 56 terms.  Corpus, index and query log are
generated from ``--seed``; nothing is downloaded.  One chip's run:

  1. builds the corpus and the impact-ordered index on the host, labels
     the query log with MED envelope labels and trains one cascade per
     knob (rho, k);
  2. for each knob, serves a few batches through ``RetrievalService`` in
     both lifecycles — batch-once (``EngineBackend``, as
     ``python -m repro.launch.serve`` builds it) and continuous
     (``ContinuousBackend``, the slot scheduler) — and checks every
     ranked list against ``RetrievalServer.serve_batch_reference`` on the
     same queries, bit for bit.

``--four-chips`` runs only the sharded path: a ``data=1, model=4`` mesh
over the same corpus, both knobs, batch-once and continuous, each ranked
list checked bit for bit against the one-chip engine on
``jax.devices()[0]``.  It trains on a 128-query log and serves one batch
per lifecycle.

Each phase prints one JSON line (seconds, compiles, top-k routes, peak
device bytes).  The last line is ``{"ok": true, "device": {...}}``.  The
script exits non-zero, and prints no such line, where JAX finds no TPU,
on any failed request or warmup, on any mismatch, and where the engine
would not run its Pallas kernels compiled.
"""

from __future__ import annotations

import argparse
import gc
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))

#: one chip's doc-range shard of MS MARCO passage ranking (8,841,823
#: passages over 4 chips).  Vocabulary as ``launch/serve.py`` derives it
#: (2 x n_docs); labelling depths and pool as that driver sets them.
DEPLOYMENT = dict(n_docs=2_210_456, mean_doc_len=56.0, stream_cap=4096,
                  n_queries=512, query_batch=64, pool_depth=2000,
                  gold_depth=200)
BATCH = 64             # requests per served batch
N_BATCHES = 2          # served batches per (knob, lifecycle)
RERANK_DEPTH = 100
TAU = 0.05             # MED envelope of the labels (launch/serve default)


class SmokeFailure(RuntimeError):
    """A phase produced a wrong or failed result."""


_T0 = time.perf_counter()


def report(phase: str, **fields) -> None:
    """One JSON line per phase; ``at`` is seconds since the script began."""
    print(json.dumps({"phase": phase, **fields,
                      "at": round(time.perf_counter() - _T0, 3)},
                     default=str), flush=True)


def peak_bytes(devices) -> list:
    """``peak_bytes_in_use`` of each device (None where not reported)."""
    out = []
    for d in devices:
        stats = d.memory_stats() or {}
        out.append(stats.get("peak_bytes_in_use"))
    return out


def build_system(size: dict, seed: int):
    """Corpus, index and query log, then one trained cascade per knob."""
    from repro.core import cascade as cascade_lib
    from repro.core import experiment as E
    from repro.core import labeling

    t0 = time.perf_counter()
    sys_ = E.build_system(E.ExperimentConfig(
        n_docs=size["n_docs"], vocab=2 * size["n_docs"],
        n_queries=size["n_queries"], mean_doc_len=size["mean_doc_len"],
        seed=seed, stream_cap=size["stream_cap"],
        gold_depth=size["gold_depth"], pool_depth=size["pool_depth"],
        query_batch=size["query_batch"]))
    report("build", seconds=time.perf_counter() - t0,
           n_docs=sys_.corpus.n_docs, postings=sys_.index.nnz,
           vocab=sys_.index.vocab, n_queries=sys_.queries.n_queries)
    cascades = {}
    for knob in ("rho", "k"):
        t0 = time.perf_counter()
        cutoffs = sys_.rho_cutoffs if knob == "rho" else sys_.k_cutoffs
        med = E.med_tables(sys_, knob, metrics=("rbp",))["rbp"]
        labels = labeling.envelope_labels(med, TAU)
        cascades[knob] = cascade_lib.train_cascade(
            sys_.features, labels, n_cutoffs=len(cutoffs),
            forest_kwargs=dict(n_trees=10, max_depth=6))
        report(f"label+train:{knob}", seconds=time.perf_counter() - t0,
               cutoffs=list(cutoffs))
    return sys_, cascades


def make_server(sys_, casc, knob: str, mesh=None):
    from repro.serving import pipeline as sp
    cutoffs = sys_.rho_cutoffs if knob == "rho" else sys_.k_cutoffs
    return sp.RetrievalServer(sys_.index, casc, sp.ServingConfig(
        knob=knob, cutoffs=cutoffs, rerank_depth=RERANK_DEPTH,
        stream_cap=sys_.cfg.stream_cap), mesh=mesh)


def check_engine(engine, expect_compiled: bool) -> None:
    """On the chip the engine must run its kernels, compiled."""
    if expect_compiled and (engine.use_kernel is not True
                            or engine.interpret is not False):
        raise SmokeFailure(
            f"engine would not run compiled kernels (use_kernel="
            f"{engine.use_kernel}, interpret={engine.interpret})")


def serve_lifecycle(server, lifecycle: str, batches: list, *,
                    label: str, devices) -> list:
    """Serve each batch through ``RetrievalService`` over the lifecycle's
    backend; returns the ranked lists, one (BATCH, depth) array per
    batch.  Warmup compiles first, as its own phase."""
    import numpy as np

    from repro.launch.serve import build_service
    from repro.serving.engine import ShardedServingEngine
    from repro.serving.service import (ContinuousBackend, EngineBackend,
                                       ShardedEngineBackend)

    engine = server.engine
    qlen = batches[0].shape[1]
    if lifecycle == "batch-once":
        cls = (ShardedEngineBackend
               if isinstance(engine, ShardedServingEngine)
               else EngineBackend)
        backend = cls(server, query_len=qlen)
    else:
        backend = ContinuousBackend(server, query_len=qlen, slots=BATCH)

    def service():
        return build_service(backend, batch=BATCH, deadline_ms=1e6,
                             census="")

    t0, c0 = time.perf_counter(), engine.n_compiles
    svc = service()
    svc.warmup_now([BATCH])
    report(f"warmup:{label}", seconds=time.perf_counter() - t0,
           compiles=engine.n_compiles - c0,
           topk_routes=dict(engine.topk_routes),
           use_kernel=engine.use_kernel, interpret=engine.interpret,
           peak_bytes=peak_bytes(devices))

    out = []
    t0, c0 = time.perf_counter(), engine.n_compiles
    for qt in batches:
        if lifecycle == "continuous":
            # the scheduler keys stage-2 noise on arrival index, which the
            # reference numbers from 0 per batch: a fresh queue per batch
            svc = service()
        results = svc.serve_all(list(qt), deadline_ms=1e6)
        if svc.warmup.failed:
            raise SmokeFailure(f"{label}: warmup failed for padded "
                               f"shapes {sorted(svc.warmup.failed)}")
        out.append(np.stack([r["ranked"] for r in results]))
    report(f"serve:{label}", seconds=time.perf_counter() - t0,
           compiles=engine.n_compiles - c0, batches=len(batches),
           requests=sum(len(b) for b in batches),
           peak_bytes=peak_bytes(devices))
    return out


def compare(label: str, got: list, want: list) -> None:
    import numpy as np
    for bi, (g, w) in enumerate(zip(got, want, strict=True)):
        if g.shape != w.shape or not np.array_equal(g, w):
            bad = (int((g != w).any(axis=1).sum())
                   if g.shape == w.shape else g.shape)
            raise SmokeFailure(f"{label}: batch {bi} differs from its "
                               f"reference ({bad} rows)")
    report(f"compare:{label}", bit_identical=True, batches=len(got))


def run_one_chip(size: dict, seed: int, *, n_batches: int = N_BATCHES,
                 expect_compiled: bool = True) -> None:
    """Both knobs x both lifecycles on ``jax.devices()[0]``, each ranked
    list checked against ``serve_batch_reference``."""
    import jax

    devices = jax.devices()[:1]
    sys_, cascades = build_system(size, seed)
    batches = [sys_.queries.terms[i * BATCH:(i + 1) * BATCH]
               for i in range(n_batches)]
    for knob in ("rho", "k"):
        t0 = time.perf_counter()
        server = make_server(sys_, cascades[knob], knob)
        check_engine(server.engine, expect_compiled)
        report(f"server:{knob}", seconds=time.perf_counter() - t0,
               peak_bytes=peak_bytes(devices))
        t0 = time.perf_counter()
        want = [server.serve_batch_reference(qt)["ranked"]
                for qt in batches]
        report(f"reference:{knob}", seconds=time.perf_counter() - t0,
               peak_bytes=peak_bytes(devices))
        for lifecycle in ("batch-once", "continuous"):
            label = f"{knob}/{lifecycle}"
            got = serve_lifecycle(server, lifecycle, batches, label=label,
                                  devices=devices)
            compare(label, got, want)
        del server
        gc.collect()


def run_four_chips(size: dict, seed: int, *,
                   n_batches: int = N_BATCHES,
                   expect_compiled: bool = True) -> None:
    """The doc-range-sharded engine on a ``data=1, model=4`` mesh, both
    knobs and both lifecycles, against the one-chip engine."""
    import jax

    from repro.distrib.sharding import make_compat_mesh

    devices = jax.devices()[:4]
    if len(devices) < 4:
        raise SmokeFailure(f"--four-chips needs 4 devices, found "
                           f"{len(jax.devices())}")
    mesh = make_compat_mesh((1, 4), ("data", "model"), devices=devices)
    sys_, cascades = build_system(size, seed)
    batches = [sys_.queries.terms[i * BATCH:(i + 1) * BATCH]
               for i in range(n_batches)]
    for knob in ("rho", "k"):
        single = make_server(sys_, cascades[knob], knob)
        check_engine(single.engine, expect_compiled)
        want = serve_lifecycle(single, "batch-once", batches,
                               label=f"{knob}/one-chip", devices=devices)
        del single
        gc.collect()
        t0 = time.perf_counter()
        server = make_server(sys_, cascades[knob], knob, mesh=mesh)
        eng = server.engine
        check_engine(eng, expect_compiled)
        report(f"server:{knob}/sharded", seconds=time.perf_counter() - t0,
               mesh=dict(mesh.shape), shard_width=eng.shard_width,
               shard_cap=eng.shard_cap,
               doc_len_spec=str(eng.doc_len.sharding.spec),
               doc_len_devices=len(eng.doc_len.sharding.device_set),
               peak_bytes=peak_bytes(devices))
        for lifecycle in ("batch-once", "continuous"):
            label = f"{knob}/sharded-{lifecycle}"
            got = serve_lifecycle(server, lifecycle, batches, label=label,
                                  devices=devices)
            compare(label, got, want)
        del server
        gc.collect()


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the 4-way sharded engine against one "
                         "chip")
    ap.add_argument("--seed", type=int, default=7)
    args = ap.parse_args(argv)

    import jax
    devices = jax.devices()
    dev = devices[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices)}
    print(f"platform={dev.platform} device_kind={dev.device_kind} "
          f"count={len(devices)}", flush=True)
    if dev.platform != "tpu":
        print(f"chip_smoke: JAX found no TPU (platform {dev.platform!r}); "
              "this smoke runs only on the chip", file=sys.stderr)
        return 2

    from repro.launch.compile_cache import use_compile_cache
    report("compile-cache", dir=use_compile_cache())
    t0 = time.perf_counter()
    if args.four_chips:
        # four chips cost four times as much per second: a shorter query
        # log and one batch per lifecycle (traffic, not width, is cut)
        run_four_chips(dict(DEPLOYMENT, n_queries=2 * BATCH), args.seed,
                       n_batches=1)
    else:
        run_one_chip(DEPLOYMENT, args.seed)
    report("total", seconds=time.perf_counter() - t0)
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
