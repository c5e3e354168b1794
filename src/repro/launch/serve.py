"""Serving driver: the multi-stage retrieval system behind the unified
async RetrievalService front door.

  PYTHONPATH=src python -m repro.launch.serve --knob k --batches 8

Requests are submitted one at a time with per-request deadlines; the
admission queue forms deadline-ordered batches over the pad grid, the
cascade prediction for batch N+1 overlaps the engine dispatch of batch N,
and the warmup policy pre-compiles the padded shapes the queue actually
produces.  ``--shards N`` serves through the mesh-sharded engine
(candidate universe over 'model', request batches over ('pod','data'))
via ``ShardedEngineBackend`` — on CPU pair it with
``--force-host-devices`` to emulate the pod.  Reports latency percentiles
with the queue-delay vs service-time breakdown, mean parameter, and
envelope compliance.

The warmup policy persists its padded-shape census to ``--census`` on
``stop()`` and reloads it at construction, so a redeploy pre-compiles
the previous run's shape distribution in the background with no explicit
batch-size list.

The process exits non-zero when any request or any warmup compile
failed.  JAX's persistent compilation cache is on: where
``JAX_COMPILATION_CACHE_DIR`` is set it decides, otherwise the cache is
``<checkout>/.jax_cache`` (``launch/compile_cache.py``).

``--online`` closes the adaptation loop (src/repro/online): the service
taps per-request telemetry into a ring buffer, a background shadow
thread re-runs sampled queries at full fidelity on idle capacity and
labels them judgment-free (MED vs the system's own reference run), a
trainer refits the cascade on sliding label windows, and retrained
weights hot-swap into the jitted predict path with zero recompiles.
"""

from __future__ import annotations

import argparse

import numpy as np


def build_service(backend, *, batch: int, deadline_ms: float,
                  census: str = "", telemetry=None, obs=None):
    """The ``RetrievalService`` this driver serves through, over any
    backend (``EngineBackend``, ``ShardedEngineBackend`` or the slot
    scheduler's ``ContinuousBackend``).  ``census`` is the warmup
    policy's padded-shape census file ('' keeps none)."""
    from repro.serving.admission import AdmissionConfig
    from repro.serving.service import RetrievalService, WarmupPolicy
    return RetrievalService(
        backend,
        AdmissionConfig(max_batch=batch,
                        pad_multiple=backend.pad_multiple,
                        default_deadline_ms=deadline_ms),
        # the census reloads the previous run's padded-shape
        # distribution, so the background thread pre-compiles it at
        # deploy time; warmup_now covers the first-boot case
        warmup=WarmupPolicy(census_path=census or None),
        telemetry=telemetry, obs=obs)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--knob", default="k", choices=["k", "rho"])
    ap.add_argument("--tau", type=float, default=0.05)
    ap.add_argument("--threshold", type=float, default=0.75)
    ap.add_argument("--batch", type=int, default=128)
    ap.add_argument("--batches", type=int, default=8)
    ap.add_argument("--deadline-ms", type=float, default=100.0)
    ap.add_argument("--n-docs", type=int, default=8000)
    ap.add_argument("--n-queries", type=int, default=1024)
    ap.add_argument("--shards", type=int, default=1,
                    help="model-axis shards for the candidate dimension")
    ap.add_argument("--data-shards", type=int, default=1,
                    help="data-axis shards for request batches")
    ap.add_argument("--force-host-devices", type=int, default=0,
                    help="emulate N CPU devices (set before first JAX use)")
    ap.add_argument("--census", default="artifacts/warmup_census.json",
                    help="padded-shape census path ('' disables "
                         "persistence)")
    ap.add_argument("--online", action="store_true",
                    help="run the shadow-label/retrain/hot-swap loop on "
                         "idle capacity")
    ap.add_argument("--shadow-sample", type=int, default=None,
                    help="logged queries labeled per shadow cycle "
                         "(default: --batch, so the shadow re-runs pad "
                         "to the already-warmed shape and compile "
                         "nothing)")
    ap.add_argument("--retrain-every", type=int, default=64,
                    help="new shadow labels between cascade refits")
    ap.add_argument("--trace-out", default="",
                    help="write a Chrome-trace/Perfetto JSON of the run "
                         "here (atomic tmp+rename; '' disables)")
    ap.add_argument("--metrics-snapshot", default="",
                    help="append one JSONL metrics snapshot here on exit "
                         "('' disables)")
    args = ap.parse_args()

    from repro.launch import mesh as mesh_lib
    from repro.launch.compile_cache import use_compile_cache
    if args.force_host_devices:
        # before anything touches a jax device: the flag only works if
        # the backends have not initialized yet
        mesh_lib.force_host_device_count(args.force_host_devices)
    use_compile_cache()

    from repro.core import cascade as cascade_lib
    from repro.core import experiment as E
    from repro.core import labeling, tradeoff
    from repro.obs import NULL_OBS, Observability, export as obs_export
    from repro.online import (OnlineConfig, OnlineController,
                              TelemetryBuffer, TrainerConfig)
    from repro.serving import pipeline as sp
    from repro.serving.service import EngineBackend, ShardedEngineBackend

    mesh = None
    if args.shards > 1 or args.data_shards > 1:
        mesh = mesh_lib.make_serving_mesh(n_model=args.shards,
                                          n_data=args.data_shards)

    sys_ = E.build_system(E.ExperimentConfig(
        n_docs=args.n_docs, vocab=args.n_docs * 2,
        n_queries=args.n_queries, stream_cap=1024, pool_depth=2000,
        gold_depth=200, query_batch=128))
    cutoffs = sys_.k_cutoffs if args.knob == "k" else sys_.rho_cutoffs
    med = E.med_tables(sys_, args.knob, metrics=("rbp",))["rbp"]
    labels = np.asarray(labeling.envelope_labels(med, args.tau))
    casc = cascade_lib.train_cascade(
        sys_.features, labels, n_cutoffs=len(cutoffs),
        forest_kwargs=dict(n_trees=10, max_depth=6))
    server = sp.RetrievalServer(
        sys_.index, casc, sp.ServingConfig(
            knob=args.knob, cutoffs=cutoffs, threshold=args.threshold,
            rerank_depth=100, stream_cap=sys_.cfg.stream_cap),
        mesh=mesh)
    backend_cls = ShardedEngineBackend if mesh is not None else EngineBackend
    backend = backend_cls(server,
                          query_len=sys_.queries.terms.shape[1])
    if mesh is not None:
        print(f"mesh: {dict(mesh.shape)} — candidates over 'model', "
              f"batches over data axes (pad grid {backend.pad_multiple})")
    # one observability handle threads through every layer (service,
    # admission, engine, scheduler, online controller); disabled unless
    # an export flag asks for it, so the default path records nothing
    obs = (Observability.create()
           if args.trace_out or args.metrics_snapshot else NULL_OBS)
    service = build_service(
        backend, batch=args.batch, deadline_ms=args.deadline_ms,
        census=args.census,
        telemetry=TelemetryBuffer() if args.online else None, obs=obs)
    service.warmup_now([args.batch])       # deploy-time shape; the
    # warmup policy keeps compiling whatever shapes admission produces

    controller = None
    if args.online:
        controller = OnlineController(service, server, OnlineConfig(
            tau=args.tau,
            shadow_sample=args.shadow_sample or args.batch,
            trainer=TrainerConfig(
                retrain_every=args.retrain_every,
                min_labels=args.retrain_every,
                forest_kwargs=dict(n_trees=10, max_depth=6))))
        controller.start()

    qn = sys_.queries.n_queries
    with service:
        print(f"{'batch':>6}{'p50_ms':>9}{'q/s':>8}"
              f"{'mean_' + args.knob:>10}{'in_envelope':>12}"
              f"{'queue_p50':>11}")
        for bi in range(args.batches):
            lo = (bi * args.batch) % max(qn - args.batch, 1)
            qt = sys_.queries.terms[lo:lo + args.batch]
            results = service.serve_all(list(qt),
                                        deadline_ms=args.deadline_ms)
            classes = np.array([r["class"] for r in results])
            pct = tradeoff.pct_under_target(
                med[lo:lo + args.batch], classes, args.tau)
            lat_s = np.mean([r["total_ms"] for r in results]) / 1e3
            batch_p50 = float(np.percentile(
                [r["total_ms"] for r in results], 50))
            print(f"{bi:>6}{batch_p50:>9.1f}"
                  f"{args.batch / max(lat_s, 1e-9):>8.0f}"
                  f"{np.mean([r['width'] for r in results]):>10.0f}"
                  f"{pct:>11.1%}"
                  f"{np.percentile([r['queue_ms'] for r in results], 50):>10.1f}")
        if controller is not None:
            # stop the adaptation thread while the service (and its
            # engine) is still up — a daemon abandoned mid-dispatch
            # aborts interpreter teardown — then drain the telemetry
            # ring inline: under saturation the idle-gated background
            # loop may never have found a window
            controller.stop()
            for _ in range(8):
                before = controller.trainer.n_labels
                controller.step()
                if controller.trainer.n_labels == before:
                    break
    if controller is not None:
        st = controller.stats()
        print(f"online: labels={st['n_labels']} "
              f"retrains={st['n_retrains']} swaps={st['n_swaps']} "
              f"version={st['predictor_version']} "
              f"tau_eff={st['tau_effective']:.3f} "
              f"med_ema={st['med_ema']:.4f} fallback={st['fallback']}"
              + (f" last_error={st['last_error']}"
                 if st["last_error"] else ""))
    print(service.stats().summary())
    print("warmed shapes:", sorted(service.warmup.compiled),
          "| shape census:", dict(service.queue.shape_counts),
          "| census file:", args.census or "(disabled)")
    if args.trace_out:
        payload = obs_export.write_chrome_trace(args.trace_out, obs.trace)
        n_x = sum(1 for e in payload["traceEvents"] if e["ph"] == "X")
        print(f"trace: {n_x} spans -> {args.trace_out} "
              f"(recorder {obs.trace.counts()})")
    if args.metrics_snapshot:
        obs_export.write_metrics_snapshot(
            args.metrics_snapshot, obs.metrics,
            extra={"argv_knob": args.knob, "batches": args.batches})
        print(f"metrics snapshot -> {args.metrics_snapshot}")
    # a failed request already raised out of serve_all; a failed warmup
    # compile is only recorded, and must not pass for a clean run
    if service.warmup.failed:
        for shape, err in sorted(service.warmup.failed.items()):
            print(f"warmup of padded batch {shape} failed: {err!r}")
        raise SystemExit(1)


if __name__ == "__main__":
    main()
