"""Serving benchmarks: dynamic vs fixed wall-clock under single dispatch,
plus the async admission path (RetrievalService) end to end.

The honest comparison the paper's efficiency claim needs: the dynamic
path (cascade prediction + traced per-query parameter) must not cost more
wall-clock than serving everyone at the fixed maximum parameter.  With
the single-dispatch engine both paths share the same executables, so the
dynamic overhead is exactly the cascade forward pass — reported here as
per-stage timings plus the executable-cache size (compile count).

The continuous-batching race (``bench_continuous_scheduler``) runs the
same query stream through the slot-table scheduler twice — per-query
predicted ρ vs everyone at the fixed maximum — and counts the chunk
dispatches each arm executes.  Early retirement makes the dynamic arm's
count scale with the *predicted* work, which is where dynamic beats
fixed on wall clock instead of merely tying it.

Machine-readable output follows the BENCH_kernels/BENCH_online split:
``artifacts/BENCH_serving.json`` is the small *committed* summary —
deterministic dispatch/retirement counts and acceptance booleans,
written at the CI smoke scale and diff-checked by bench-smoke — while
the gitignored ``artifacts/BENCH_serving_full.json`` carries the
per-machine timings (p50/p99, queue-vs-service breakdown, per-stage ms,
throughput).  ``--smoke`` runs the tiny scale for CI.
"""

from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import textwrap
import time

import numpy as np

ART = os.path.join(os.path.dirname(__file__), "..", "artifacts")
BENCH_JSON = os.path.join(ART, "BENCH_serving.json")
FULL_JSON = os.path.join(ART, "BENCH_serving_full.json")

#: filled by bench_continuous_scheduler / bench_paced_deadlines; the
#: committed summary is assembled from these (deterministic fields only)
_RECORDS: dict = {"scheduler": None, "deadline": None, "sharded": None,
                  "knobs": None, "obs": None}


def _build_server():
    from benchmarks import common
    from repro.core import cascade as cl
    from repro.core import labeling
    from repro.serving import pipeline as sp

    sys_ = common.get_system()
    m = common.get_med("k")["rbp"]
    labels = np.asarray(labeling.envelope_labels(m, 0.05))
    casc = cl.train_cascade(sys_.features, labels,
                            n_cutoffs=len(sys_.k_cutoffs),
                            forest_kwargs=common.forest_kwargs())
    cfg = sp.ServingConfig(knob="k", cutoffs=sys_.k_cutoffs,
                           threshold=0.75, rerank_depth=100,
                           stream_cap=sys_.cfg.stream_cap)
    return sys_, sp.RetrievalServer(sys_.index, casc, cfg)


def bench_dynamic_vs_fixed() -> list[tuple]:
    """Acceptance row: dynamic wall-clock at or below fixed max-param."""
    sys_, server = _build_server()
    qt = sys_.queries.terms[:256]
    qlen = qt.shape[1]
    server.engine.warmup([256], qlen)     # compile off the timed path

    def best_of(fn, n=3):
        ts = []
        for _ in range(n):
            t0 = time.time()
            fn()
            ts.append(time.time() - t0)
        return min(ts)

    server.serve_batch(qt)                # cascade jit warmup
    dyn_s = best_of(lambda: server.serve_batch(qt))
    fix_s = best_of(lambda: server.serve_fixed(qt, sys_.k_cutoffs[-1]))
    out = server.serve_batch(qt)
    rows = [
        ("serving/dynamic_single_dispatch_256q", dyn_s / 256 * 1e6,
         f"mean_k={out['mean_param']:.0f}"),
        ("serving/fixed_max_single_dispatch_256q", fix_s / 256 * 1e6,
         f"mean_k={sys_.k_cutoffs[-1]}"),
        ("serving/dynamic_vs_fixed_ratio", dyn_s / fix_s,
         "PASS" if dyn_s <= fix_s * 1.05 else "FAIL"),
        ("serving/executable_cache", server.engine.n_compiles,
         "compiles (constant in class diversity)"),
    ]
    for key, ms in out["timings"].items():
        stage = key.removesuffix("_ms")
        rows.append((f"serving/stage_{stage}_us", ms * 1e3,
                     "per 256q batch"))
    return rows


def bench_compile_amortization() -> list[tuple]:
    """Per-bucket reference vs single dispatch on a many-bucket batch."""
    sys_, server = _build_server()
    qt = sys_.queries.terms[:128]
    server.serve_batch(qt)                # warm both paths
    server.serve_batch_reference(qt)
    t0 = time.time()
    server.serve_batch(qt)
    dyn_s = time.time() - t0
    t0 = time.time()
    out_ref = server.serve_batch_reference(qt)
    ref_s = time.time() - t0
    n_buckets = len(set(out_ref["classes"].tolist()))
    return [
        ("serving/single_dispatch_128q", dyn_s / 128 * 1e6,
         f"{n_buckets}_live_buckets"),
        ("serving/per_bucket_reference_128q", ref_s / 128 * 1e6,
         f"{n_buckets}_live_buckets"),
    ]


def bench_admission_service() -> list[tuple]:
    """The unified async path: deadline-driven admission end to end.

    Feeds a query stream through RetrievalService (threaded: prediction
    for batch N+1 overlapping dispatch of batch N) and reports request
    latency percentiles with the queue-vs-service breakdown the
    deployment loop tunes deadlines against.
    """
    from repro.serving.admission import AdmissionConfig
    from repro.serving.service import EngineBackend, RetrievalService

    sys_, server = _build_server()
    n_stream = min(512, sys_.queries.n_queries)
    qt = sys_.queries.terms[:n_stream]
    backend = EngineBackend(server, query_len=qt.shape[1])
    service = RetrievalService(backend, AdmissionConfig(
        max_batch=128, pad_multiple=server.cfg.pad_multiple,
        max_wait_ms=2.0, default_deadline_ms=100.0))
    service.warmup_now([128])             # deploy-time shape
    with service:
        service.serve_all(list(qt[:128]))     # cascade jit warmup
        t0 = time.time()
        results = service.serve_all(list(qt))
        wall_s = time.time() - t0
    # total_ms spans submit -> resolve (incl. the predict/execute handoff
    # wait), the same clock deadline_met is judged against
    lat = [r["total_ms"] for r in results]
    met = np.mean([r["deadline_met"] for r in results])
    return [
        ("serving/admission_request_p50_ms", float(np.percentile(lat, 50)),
         f"{n_stream}q_stream"),
        ("serving/admission_request_p99_ms", float(np.percentile(lat, 99)),
         f"deadline_met={met:.0%}"),
        ("serving/admission_queue_p50_ms",
         float(np.percentile([r["queue_ms"] for r in results], 50)),
         "admission delay"),
        ("serving/admission_service_p50_ms",
         float(np.percentile([r["service_ms"] for r in results], 50)),
         "backend execute"),
        ("serving/admission_throughput_qps", n_stream / wall_s,
         f"shapes={sorted(service.queue.shape_counts)}"),
        ("serving/admission_warmed_shapes", len(service.warmup.compiled),
         "learned warmup policy"),
    ]


def _hash_rows(qt):
    qt = np.asarray(qt)
    return np.where(qt >= 0, qt, 0).sum(axis=1) + (qt >= 0).sum(axis=1)


def _build_knob_server(primary: str, *, with_depth: bool = False):
    """A continuous-race server on the chosen primary knob (rho = the
    anytime-work knob the scheduler retires against, k = the pool-width
    knob), classes *stubbed* as content hashes; ``with_depth`` also
    registers the depth knob, stubbed from a decorrelated hash.

    The stubs are deliberate: the committed summary carries dispatch and
    stage-2 row counts, and integer-hash classes make them
    platform-exact, where a trained forest's float thresholds could flip
    a borderline query between classes across BLAS builds and dirty the
    diff-checked file.  The cascade's forward cost is measured by
    bench_dynamic_vs_fixed; these benches isolate what early retirement
    and prefix-masked reranking save."""
    from benchmarks import common
    from repro.core import knobs as knobs_lib
    from repro.serving import pipeline as sp

    sys_ = common.get_system()
    cuts = sys_.rho_cutoffs if primary == "rho" else sys_.k_cutoffs
    dgrid = None
    if with_depth:
        pool = 100 if primary == "rho" else int(max(cuts))
        dgrid = knobs_lib.depth_cutoffs(pool)
    cfg = sp.ServingConfig(knob=primary, cutoffs=cuts, rerank_depth=100,
                           stream_cap=sys_.cfg.stream_cap,
                           depth_cutoffs=dgrid)
    server = sp.RetrievalServer(sys_.index, None, cfg)
    n_cls = len(cuts) + 1
    real = server.predict_classes

    def classes_of(qt, knob=None):
        if knob not in (None, primary):    # depth etc.: real registry
            return real(qt, knob=knob)
        return (_hash_rows(qt) % n_cls).astype(np.int64)

    server.predict_classes = classes_of
    if with_depth:
        n_dcls = len(dgrid) + 1

        def pdepth(qt):
            # decorrelated from the primary hash so mixed primary/depth
            # buckets genuinely co-occur in one slot table
            cls = ((_hash_rows(qt) // 3) % n_dcls).astype(np.int64)
            return cls, server.params_of(cls, knob="depth")

        server.predict_depths = pdepth
    return sys_, server


def _build_rho_server():
    return _build_knob_server("rho")


def _continuous_run(server, qt, *, fixed_param=None, slots=8, grain=8):
    # a small table on purpose: the chunk program spans the whole slot
    # table, so the dispatch count (the wall-clock driver on the oracle
    # path, where masked rows still cost) only tracks the per-query
    # window savings when the table drains often enough to refill —
    # at slots=grain the race measures retirement, not idle capacity
    from repro.serving.service import ContinuousBackend, RetrievalService

    backend = ContinuousBackend(server, query_len=qt.shape[1],
                                slots=slots, grain=grain,
                                fixed_param=fixed_param)
    svc = RetrievalService(backend)
    backend.scheduler.warmup()            # compile off the timed path
    t0 = time.perf_counter()
    results = svc.serve_all(list(qt), deadline_ms=1e9)
    wall_s = time.perf_counter() - t0
    return backend, results, wall_s


def bench_continuous_scheduler() -> list[tuple]:
    """The dynamic-vs-fixed race, continuous-batching edition.

    Same slot table, same four executables, same stream: the dynamic arm
    retires each query once its predicted ρ is exhausted, the fixed arm
    runs everyone to the maximum.  Reports chunk-dispatch counts (the
    deterministic mechanism) and the wall-clock ratio (the observable
    win), plus bit-identity against the batch-once engine and compile
    flatness across ragged churn."""
    sys_, server = _build_rho_server()
    n = min(192, sys_.queries.n_queries)
    qt = sys_.queries.terms[:n]
    cap = int(sys_.cfg.stream_cap)

    dyn_b, dyn_out, dyn_s = _continuous_run(server, qt)
    fix_b, fix_out, fix_s = _continuous_run(server, qt, fixed_param=cap)

    # bit-identity of the dynamic arm vs one batch-once serve
    classes = np.asarray(server.predict_classes(qt))
    ranked_ref, _ = server.engine.serve(qt, server.params_of(classes))
    bit_identical = all(
        np.array_equal(res["ranked"], ranked_ref[i])
        for i, res in enumerate(dyn_out))

    # compile flatness across ragged admit/retire churn: a fresh service
    # over the same (already warmed) engine must add zero executables
    from repro.serving.service import ContinuousBackend, RetrievalService
    svc = RetrievalService(ContinuousBackend(
        server, query_len=qt.shape[1], slots=8, grain=8))
    n0 = server.engine.n_compiles
    for size in (1, 5, 8, 3, 7, 2, 6, 4):
        svc.serve_all(list(qt[:size]), deadline_ms=1e9)
    churn_compiles = server.engine.n_compiles - n0

    dyn_windows = sum(res["chunks_executed"] for res in dyn_out)
    fix_windows = sum(res["chunks_executed"] for res in fix_out)
    dyn_st = dyn_b.scheduler.stats()
    fix_st = fix_b.scheduler.stats()
    ratio = dyn_windows / fix_windows
    _RECORDS["scheduler"] = {
        "knob": "rho",
        "n_queries": int(n),
        "slots": dyn_st["slots"],
        "grain": dyn_st["grain"],
        "chunk_p": dyn_st["chunk_p"],
        "chunks_max": dyn_st["chunks_max"],
        "dynamic_chunk_windows": int(dyn_windows),
        "fixed_chunk_windows": int(fix_windows),
        "dynamic_vs_fixed_ratio": round(ratio, 4),
        "dynamic_chunk_dispatches": dyn_st["n_chunk_calls"],
        "fixed_chunk_dispatches": fix_st["n_chunk_calls"],
        "retire_reasons": dyn_st["retire_reasons"],
        "dynamic_wins_wall_clock": bool(dyn_s < fix_s),
        "bit_identical_to_batch_once": bool(bit_identical),
        "zero_compiles_under_churn": bool(churn_compiles == 0),
    }
    return [
        ("serving/continuous_dynamic_qps", n / dyn_s,
         f"mean_rho={np.mean([r['width'] for r in dyn_out]):.0f}"),
        ("serving/continuous_fixed_qps", n / fix_s, f"rho={cap}"),
        ("serving/continuous_window_ratio", ratio,
         f"{dyn_windows}/{fix_windows} chunk windows"),
        ("serving/continuous_dispatch_ratio",
         dyn_st["n_chunk_calls"] / fix_st["n_chunk_calls"],
         f"{dyn_st['n_chunk_calls']}/{fix_st['n_chunk_calls']} dispatches"),
        ("serving/continuous_wall_ratio", dyn_s / fix_s,
         "PASS" if dyn_s < fix_s else "FAIL"),
        ("serving/continuous_bit_identical", float(bit_identical),
         "PASS" if bit_identical else "FAIL"),
        ("serving/continuous_churn_compiles", churn_compiles,
         "PASS" if churn_compiles == 0 else "FAIL"),
    ]


def bench_three_knob_depth() -> list[tuple]:
    """The three-knob race: per-query depth riding the continuous
    scheduler on each primary knob (rho and k).

    The dynamic arm predicts both the primary parameter and the
    reranking depth per query (content-hash stubs — see
    ``_build_knob_server``); the fixed arm serves everyone at the
    primary's reference with the depth knob off.  Committed fields: the
    stage-2 row fraction the depth mask actually scores (the knob's
    deterministic win — the scheduler counts rows at retirement), the
    per-knob retirement histograms, and the MED acceptance of the
    dynamic arm against its own full-fidelity reference."""
    import jax.numpy as jnp

    from repro.core import med as med_lib
    from repro.online.shadow import reference_param

    rec: dict = {"three_knob_grids": {},
                 "stage2_rows_scored_fraction": {},
                 "knob_retirement_counts": {},
                 "three_knob_window_ratio": {},
                 "dynamic_mean_med": {},
                 "dynamic_inside_med_envelope": {},
                 "three_knob_bit_identical": True}
    rows: list[tuple] = []
    for primary in ("rho", "k"):
        sys_, server = _build_knob_server(primary, with_depth=True)
        n = min(96, sys_.queries.n_queries)
        qt = sys_.queries.terms[:n]
        ref_p = reference_param(server.cfg)

        dyn_b, dyn_out, dyn_s = _continuous_run(server, qt)
        _, fix_server = _build_knob_server(primary)   # depth knob off
        fix_b, fix_out, fix_s = _continuous_run(fix_server, qt,
                                                fixed_param=ref_p)

        # bit-identity of the dynamic arm vs one batch-once serve at
        # the same (primary, depth) vectors
        classes = np.asarray(server.predict_classes(qt))
        dcls, depths = server.predict_depths(qt)
        ranked_ref, _ = server.engine.serve(
            qt, server.params_of(classes), depth_vec=depths)
        bit_identical = all(
            np.array_equal(res["ranked"], ranked_ref[i])
            for i, res in enumerate(dyn_out))
        rec["three_knob_bit_identical"] &= bool(bit_identical)

        # MED of the dynamic run against the full-fidelity reference
        # (primary at its reference, depth unmasked) — the acceptance
        # margin is generous on purpose: hash-stub classes are a *floor*
        # for a trained cascade, and the boolean must not flip on float
        # eps across platforms
        ref = fix_server.serve_fixed(qt, ref_p)["ranked"]
        dyn = np.stack([np.asarray(r["ranked"]) for r in dyn_out])
        med = np.asarray(med_lib.med_rbp(jnp.asarray(dyn),
                                         jnp.asarray(ref), p=0.95))
        mean_med = float(med.mean())

        sch = dyn_b.scheduler.stats()
        frac = sch["n_rows_scored"] / sch["n_rows_full"]
        win_ratio = (sum(r["chunks_executed"] for r in dyn_out)
                     / sum(r["chunks_executed"] for r in fix_out))
        grid = server.cfg.depth_cutoffs
        rec["three_knob_grids"][primary] = [int(c) for c in
                                            server.cfg.cutoffs]
        rec["three_knob_grids"][f"depth@{primary}"] = [int(d)
                                                       for d in grid]
        rec["stage2_rows_scored_fraction"][primary] = round(frac, 4)
        prim_hist = {str(int(r["width"])): 0 for r in dyn_out}
        depth_hist = {str(int(r["depth"])): 0 for r in dyn_out}
        for r in dyn_out:
            prim_hist[str(int(r["width"]))] += 1
            depth_hist[str(int(r["depth"]))] += 1
        rec["knob_retirement_counts"][primary] = prim_hist
        rec["knob_retirement_counts"][f"depth@{primary}"] = depth_hist
        rec["three_knob_window_ratio"][primary] = round(win_ratio, 4)
        rec["dynamic_mean_med"][primary] = round(mean_med, 3)
        rec["dynamic_inside_med_envelope"][primary] = bool(
            mean_med <= 0.35)
        rows += [
            (f"serving/three_knob_{primary}_rows_fraction", frac,
             f"{sch['n_rows_scored']}/{sch['n_rows_full']} stage-2 rows"
             + (" PASS" if frac < 1.0 else " FAIL")),
            (f"serving/three_knob_{primary}_window_ratio", win_ratio,
             "dynamic/fixed chunk windows"),
            (f"serving/three_knob_{primary}_mean_med", mean_med,
             "PASS" if mean_med <= 0.35 else "FAIL"),
            (f"serving/three_knob_{primary}_qps", n / dyn_s,
             f"mean_depth={np.mean([r['depth'] for r in dyn_out]):.0f}"),
        ]
    _RECORDS["knobs"] = rec
    return rows


def bench_paced_deadlines() -> list[tuple]:
    """Paced open-loop arrivals against the continuous scheduler.

    The batch-once admission bench feeds a thundering herd; this one
    paces arrivals (open loop — the submitter never waits on results),
    which is the regime continuous batching exists for: requests join
    in-flight work at the next stage boundary instead of waiting for a
    batch to form, so a generous per-request deadline is met ~always."""
    from repro.serving.service import ContinuousBackend, RetrievalService

    sys_, server = _build_rho_server()
    n = min(96, sys_.queries.n_queries)
    qt = sys_.queries.terms[:n]
    deadline_ms, interval_s = 500.0, 0.002
    backend = ContinuousBackend(server, query_len=qt.shape[1],
                                slots=16, grain=8)
    svc = RetrievalService(backend)
    backend.scheduler.warmup()
    with svc:
        svc.serve_all(list(qt[:16]), deadline_ms=1e9)   # steady state
        t0 = time.perf_counter()
        futs = []
        for row in qt:
            futs.append(svc.submit(row, deadline_ms=deadline_ms))
            time.sleep(interval_s)
        results = [f.result(timeout=60) for f in futs]
        wall_s = time.perf_counter() - t0
    lat = [r["total_ms"] for r in results]
    met = float(np.mean([r["deadline_met"] for r in results]))
    _RECORDS["deadline"] = {
        "paced_n_queries": int(n),
        "paced_interval_ms": interval_s * 1e3,
        "paced_deadline_ms": deadline_ms,
        "deadline_met": met,
    }
    return [
        ("serving/paced_request_p50_ms", float(np.percentile(lat, 50)),
         f"open-loop {interval_s * 1e3:.0f}ms pacing"),
        ("serving/paced_request_p99_ms", float(np.percentile(lat, 99)),
         f"deadline_met={met:.0%}"),
        ("serving/paced_throughput_qps", n / wall_s,
         f"deadline={deadline_ms:.0f}ms"),
    ]


def sharded_vs_single(n_shards: int, n_docs: int, cap: int) -> dict:
    """One-device vs ``n_shards``-way sharded engine in *this* process,
    over the first ``n_shards`` devices the platform has: best-of-3
    queries/s of each, and whether their ranked lists are identical."""
    import jax

    from repro.core import experiment as E
    from repro.distrib.sharding import make_compat_mesh
    from repro.serving import pipeline as sp

    sys_ = E.build_system(E.ExperimentConfig(
        n_docs=n_docs, vocab=n_docs * 2, n_queries=256, stream_cap=cap,
        pool_depth=1000, gold_depth=200, query_batch=128))

    def make_server(mesh=None):
        # slack 2.5: the smoke corpus's doc skew puts up to ~0.56*cap of
        # a query's postings on one shard (measured; 2.0 overflows)
        cfg = sp.ServingConfig(knob="k", cutoffs=sys_.k_cutoffs,
                               rerank_depth=100,
                               stream_cap=sys_.cfg.stream_cap,
                               partition_slack=2.5)
        srv = sp.RetrievalServer(sys_.index, None, cfg, mesh=mesh)
        srv.predict_classes = (
            lambda qt: np.arange(qt.shape[0]) % (len(sys_.k_cutoffs) + 1))
        return srv

    def best_qps(server, qt, n=3):
        server.serve_batch(qt)            # warm
        ts = []
        for _ in range(n):
            t0 = time.perf_counter()
            server.serve_batch(qt)
            ts.append(time.perf_counter() - t0)
        return qt.shape[0] / min(ts)

    qt = sys_.queries.terms[:128]
    single = make_server()
    sharded = make_server(make_compat_mesh(
        (1, n_shards), ("data", "model"),
        devices=jax.devices()[:n_shards]))
    a = single.serve_batch(qt)["ranked"]
    b = sharded.serve_batch(qt)["ranked"]
    eng = sharded.engine
    return {
        "single_qps": best_qps(single, qt),
        "sharded_qps": best_qps(sharded, qt),
        "n_shards": n_shards,
        "bit_identical": bool(np.array_equal(a, b)),
        "stream_cap": int(eng.cfg.stream_cap),
        "shard_stream_cap": int(eng.shard_cap),
        "partition_slack": float(eng.cfg.partition_slack),
        "platform": jax.devices()[0].platform,
    }


#: CPU emulation only: a child process whose XLA flags give the CPU
#: backend ``n_dev`` devices (the flag must be set before JAX starts)
_EMULATED_SCRIPT = textwrap.dedent("""
    import json, os, sys
    os.environ["XLA_FLAGS"] = (
        "--xla_force_host_platform_device_count=%(n_dev)d")
    os.environ["JAX_PLATFORMS"] = "cpu"
    sys.path[:0] = [%(src)r, %(root)r]
    from benchmarks.bench_serving import sharded_vs_single
    print(json.dumps(sharded_vs_single(%(n_dev)d, %(n_docs)d, %(cap)d)))
""")


def bench_sharded_vs_single() -> list[tuple]:
    """Mesh-sharded engine vs single device.

    Runs in this process on the devices the platform has (one process
    per chip: a child could not reach a chip this process holds).  Only
    where the platform is the CPU and has too few devices does a child
    process emulate them with forced host devices — there the sharded
    path pays real collective overhead for no real parallel FLOPs, so
    the number tracks that overhead across PRs and says nothing about
    a chip.  Also asserts the sharded output is bit-identical.
    """
    import jax

    n_shards = int(os.environ.get("REPRO_BENCH_SHARDS", "4"))
    smoke = os.environ.get("REPRO_BENCH_SCALE") == "tiny"
    size = dict(n_docs=2000 if smoke else 8000,
                cap=512 if smoke else 2048)
    devices = jax.devices()
    if devices[0].platform != "cpu" or len(devices) >= n_shards:
        n_shards = min(n_shards, len(devices))
        out = sharded_vs_single(n_shards, **size)
    else:
        root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        script = _EMULATED_SCRIPT % dict(
            n_dev=n_shards, src=os.path.join(root, "src"), root=root,
            **size)
        r = subprocess.run([sys.executable, "-c", script],
                           capture_output=True, text=True, timeout=900)
        if r.returncode != 0:
            raise RuntimeError(
                f"sharded bench subprocess failed:\n{r.stderr}")
        out = json.loads(r.stdout.strip().splitlines()[-1])
    if not out["bit_identical"]:
        raise RuntimeError("sharded engine diverged from single-device")
    ratio = out["sharded_qps"] / out["single_qps"]
    # deterministic partition-volume counters: per-shard stream length is
    # a pure function of (stream_cap, n_shards, partition_slack), so the
    # ~1/n_shards gather/scan-volume claim is committed and diff-checked
    cap, scap = out["stream_cap"], out["shard_stream_cap"]
    frac = scap / cap
    _RECORDS["sharded"] = {
        "sharded_n_shards": int(out["n_shards"]),
        "sharded_stream_cap": int(cap),
        "sharded_shard_stream_cap": int(scap),
        "sharded_stream_fraction": round(frac, 4),
        "sharded_partition_slack": out["partition_slack"],
        # the per-shard stream carries <= slack/n_shards of the global
        # postings (modulo the 8-wide alignment of partition_cap)
        "sharded_volume_scales": bool(
            scap <= out["partition_slack"] * cap / out["n_shards"] + 8),
        "sharded_bit_identical": bool(out["bit_identical"]),
        "sharded_vs_single_throughput": round(ratio, 4),
    }
    return [
        ("serving/single_device_qps", out["single_qps"], "128q batch"),
        (f"serving/sharded_{n_shards}dev_qps", out["sharded_qps"],
         f"{out['platform']} devices, candidates over 'model'"),
        ("serving/sharded_vs_single_throughput", ratio,
         f"bit_identical={out['bit_identical']} "
         f"shard_stream={scap}/{cap}"),
    ]


def bench_obs_counters() -> list[tuple]:
    """The committed observability record: the deterministic counter
    surface of one instrumented continuous churn run (submissions,
    working ticks and retirements are pure functions of (code, stream),
    so it is diff-checked like the dispatch counts), and that the
    instrumentation compiles nothing (spans wrap dispatch boundaries,
    never traced code) and closes every span."""
    from repro.obs import Observability
    from repro.serving.service import ContinuousBackend, RetrievalService

    sys_, server = _build_rho_server()
    n = min(96, sys_.queries.n_queries)
    qt = sys_.queries.terms[:n]
    # serve_all ticks inline here — no service threads — so even the
    # working-tick count is a pure function of the stream
    obs = Observability.create(capacity=1 << 15)
    backend = ContinuousBackend(server, query_len=qt.shape[1],
                                slots=8, grain=8)
    svc = RetrievalService(backend, obs=obs)
    backend.scheduler.warmup()
    n0 = server.engine.n_compiles
    svc.serve_all(list(qt), deadline_ms=1e9)
    obs_compiles = server.engine.n_compiles - n0
    tc = obs.trace.counts()
    c = obs.metrics.counters()
    _RECORDS["obs"] = {
        "obs_zero_new_compiles": bool(obs_compiles == 0),
        "obs_spans_balanced": bool(
            tc["n_open"] == 0 and tc["n_begun"] == tc["n_ended"]),
        "obs_counters": {k: int(c[k]) for k in (
            "queue.submitted", "sched.ticks",
            "sched.retired.rho_exhausted",
            "sched.retired.stream_exhausted",
            "sched.retired.pool_complete")},
    }
    return [
        ("serving/obs_spans_per_pass", tc["n_begun"], "deterministic"),
        ("serving/obs_new_compiles", obs_compiles,
         "PASS" if obs_compiles == 0 else "FAIL"),
    ]


# ----------------------------------------------------------- JSON output --

def payload_from_rows(rows: list[tuple]) -> dict:
    """Distill the serving rows into the cross-PR trajectory record."""
    by_name = {name: (val, derived) for name, val, derived in rows}

    def val(name):
        return float(by_name[name][0]) if name in by_name else None

    stage_ms = {
        name.removeprefix("serving/stage_").removesuffix("_us"):
            float(v) / 1e3
        for name, (v, _) in by_name.items()
        if name.startswith("serving/stage_")}
    ratio = val("serving/dynamic_vs_fixed_ratio")
    n_compiles = val("serving/executable_cache")
    has_sharded = any(name.startswith("serving/sharded_")
                      or name == "serving/single_device_qps"
                      for name in by_name)
    return {
        "sharded_vs_single_device": {
            "single_qps": val("serving/single_device_qps"),
            "sharded_qps": next(
                (float(v) for name, (v, _) in by_name.items()
                 if name.startswith("serving/sharded_")
                 and name.endswith("dev_qps")), None),
            "throughput_ratio": val(
                "serving/sharded_vs_single_throughput"),
        } if has_sharded else None,
        "p50_ms": val("serving/admission_request_p50_ms"),
        "p99_ms": val("serving/admission_request_p99_ms"),
        "queue_p50_ms": val("serving/admission_queue_p50_ms"),
        "service_p50_ms": val("serving/admission_service_p50_ms"),
        "throughput_qps": val("serving/admission_throughput_qps"),
        "stage_ms": stage_ms,
        "n_compiles": None if n_compiles is None else int(n_compiles),
        "dynamic_vs_fixed_ratio": ratio,
        "dynamic_vs_fixed_speedup": None if not ratio else 1.0 / ratio,
        "rows": [[name, float(v), str(d)] for name, v, d in rows],
    }


def summary_payload() -> dict | None:
    """The committed record: deterministic counts/booleans only.

    Assembled from the continuous-scheduler race, the paced deadline
    bench and the sharded-vs-single race; every field is a pure function
    of (code, seed) — no wall clock — except the acceptance booleans
    (committed with enough margin to be machine-independent in outcome)
    and the measured sharded_vs_single_throughput, which the bench-smoke
    diff explicitly excludes."""
    if _RECORDS["scheduler"] is None:
        return None
    payload = dict(_RECORDS["scheduler"])
    payload.update(_RECORDS["deadline"] or {})
    # every sharded field is deterministic except the measured
    # sharded_vs_single_throughput, which bench-smoke excludes from the
    # exact diff (git diff -I) so the committed trajectory can move
    payload.update(_RECORDS["sharded"] or {})
    payload.update(_RECORDS["knobs"] or {})
    payload.update(_RECORDS["obs"] or {})
    return payload


def write_bench_json(rows: list[tuple], path: str | None = None) -> str:
    """Committed summary + gitignored full record (same contract as
    BENCH_online.json: the summary is defined at the CI smoke scale, so
    a default-scale run never dirties the diff-checked file)."""
    from benchmarks import common
    explicit = path is not None or "REPRO_BENCH_JSON" in os.environ
    path = path or os.environ.get("REPRO_BENCH_JSON", BENCH_JSON)
    os.makedirs(ART, exist_ok=True)
    wrote = None
    summary = summary_payload()
    if summary is not None and (explicit or common.scale_name() == "tiny"):
        summary["scale"] = common.scale_name()
        with open(path, "w") as f:
            json.dump(summary, f, indent=2, sort_keys=True)
            f.write("\n")
        wrote = path
    full = payload_from_rows(rows)
    full["summary"] = summary
    # the obs record rides along even when --only skipped the rest of
    # the suite: CI's obs-smoke diff-checks these fields against the
    # committed summary without paying for the full bench run
    full["obs"] = _RECORDS["obs"]
    full["scale"] = common.scale_name()
    full["unix_time"] = time.time()
    with open(FULL_JSON, "w") as f:
        json.dump(full, f, indent=2, sort_keys=True)
    return os.path.abspath(wrote or FULL_JSON)


BENCHES = [bench_dynamic_vs_fixed, bench_compile_amortization,
           bench_admission_service, bench_continuous_scheduler,
           bench_three_knob_depth, bench_paced_deadlines,
           bench_sharded_vs_single, bench_obs_counters]


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--smoke", action="store_true",
                    help="tiny scale, interpret mode (CI)")
    ap.add_argument("--only", default=None,
                    help="run only benches whose name contains this "
                         "substring (the committed summary needs the "
                         "full set — use for iteration, not artifacts)")
    ap.add_argument("--out", default=None,
                    help=f"JSON output path (default {BENCH_JSON})")
    args = ap.parse_args(argv)
    if args.smoke:
        os.environ["REPRO_BENCH_SCALE"] = "tiny"

    benches = [b for b in BENCHES
               if args.only is None or args.only in b.__name__]
    print("name,us_per_call,derived")
    rows: list[tuple] = []
    for b in benches:
        for row in b():
            rows.append(row)
            name, v, derived = row
            print(f"{name},{v:.1f},{derived}", flush=True)
    path = write_bench_json(rows, args.out)
    print(f"wrote {path}", file=sys.stderr)


if __name__ == "__main__":
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    main()
