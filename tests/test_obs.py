"""Unified observability layer: span balance under exceptions and
cancellation, ring-bounded memory under churn, hot-path cleanliness of
the instrumented serve (no recompiles, no transfers, bit-identical
output), deterministic counter equality across the XLA and Pallas
interpret paths, the trace_id telemetry join, exporters, and the
ServerStats per-stage p99 rendering."""

import json
import threading
import time

import numpy as np
import pytest

from repro.analysis import sanitizers as S
from repro.core import experiment as E
from repro.obs import (NULL_OBS, NULL_REGISTRY, NULL_TRACE, Observability,
                       export)
from repro.obs.metrics import MetricsRegistry
from repro.obs.trace import TraceRecorder
from repro.online.telemetry import TelemetryBuffer
from repro.serving import pipeline as serve_lib
from repro.serving import server as server_lib
from repro.serving.admission import AdmissionConfig
from repro.serving.service import (ContinuousBackend, EngineBackend,
                                   RetrievalService)


@pytest.fixture(scope="module")
def small_system():
    return E.build_system(E.ExperimentConfig(
        n_docs=400, vocab=900, n_queries=40, stream_cap=128,
        pool_depth=100, gold_depth=50, query_batch=16, seed=21))


def _hash_rows(qt):
    qt = np.asarray(qt)
    return np.where(qt >= 0, qt, 0).sum(axis=1) + (qt >= 0).sum(axis=1)


def _server(sys_, knob="k", **cfg_kw):
    cuts = sys_.k_cutoffs if knob == "k" else sys_.rho_cutoffs
    cfg = serve_lib.ServingConfig(
        knob=knob, cutoffs=cuts, rerank_depth=30,
        stream_cap=sys_.cfg.stream_cap, **cfg_kw)
    server = serve_lib.RetrievalServer(sys_.index, None, cfg)
    n_cls = len(cuts) + 1
    # content-hash stub: classes survive scheduler regrouping and are
    # identical across engines, so counters admit an equality oracle
    server.predict_classes = (
        lambda qt: (_hash_rows(qt) % n_cls).astype(np.int64))
    return server


def _balanced(trace):
    c = trace.counts()
    assert c["n_open"] == 0, trace.open_spans()
    assert c["n_begun"] == c["n_ended"]
    return c


# ------------------------------------------------------ recorder core --

def test_span_context_balances_on_exception():
    tr = TraceRecorder(capacity=16)
    with pytest.raises(ValueError):
        with tr.span("engine.stage1", qid=7):
            raise ValueError("body blew up")
    c = _balanced(tr)
    assert c["n_begun"] == 1
    (sp,) = tr.spans()
    assert sp.name == "engine.stage1" and sp.qid == 7 and sp.ended


def test_end_is_idempotent_and_none_tolerant():
    tr = TraceRecorder(capacity=16)
    h = tr.begin("request", qid=1)
    tr.end(h, deadline_met=True)
    t1 = h.t1
    tr.end(h, cancelled=True)         # loser of the resolve/cancel race
    assert h.t1 == t1 and "cancelled" not in (h.attrs or {})
    assert tr.end(None) is None       # obs-off call sites pass None
    c = _balanced(tr)
    assert c["n_begun"] == c["n_ended"] == 1


def test_ring_bounded_under_churn():
    tr = TraceRecorder(capacity=32)
    for i in range(1000):
        with tr.span("tick", tick=i):
            pass
    c = _balanced(tr)
    assert c["n_held"] == 32 and c["n_dropped"] == 1000 - 32
    ticks = [sp.tick for sp in tr.spans()]
    assert ticks == list(range(968, 1000))   # oldest evicted first


def test_disabled_recorder_still_stamps_times():
    before = NULL_TRACE.counts()
    with NULL_TRACE.span("engine.stage1") as sp:
        pass
    assert sp.ended and sp.dur_ms >= 0.0     # timings derive obs-off
    assert NULL_TRACE.record("tick", 0.0, 1.0) is None
    assert NULL_TRACE.counts() == before     # nothing recorded


def test_ctx_stamps_thread_local_join_keys():
    tr = TraceRecorder(capacity=16)
    with tr.ctx(batch=3):
        with tr.span("execute"):
            pass
        with tr.ctx(batch=4):             # nesting: innermost wins
            tr.record("predict", 0.0, 1.0)
    with tr.span("engine.stage1"):        # outside any ctx
        pass
    ex, pred, st1 = tr.spans()
    assert ex.attrs == {"batch": 3}
    assert pred.attrs == {"batch": 4}
    assert st1.attrs is None


def test_record_retrospective_and_event():
    tr = TraceRecorder(capacity=16)
    tr.record("slot", 1.0, 2.5, qid=5, slot=2, retire_reason="rho_exhausted")
    tr.event("online.fallback", step=9)
    c = _balanced(tr)
    assert c["n_begun"] == 2
    slot, ev = tr.spans()
    assert slot.dur_ms == pytest.approx(1500.0)
    assert ev.t0 == ev.t1 and ev.attrs == {"step": 9}


def test_cross_thread_begin_end_lanes():
    tr = TraceRecorder(capacity=16)
    h = tr.begin("request", qid=0)

    def work():
        tr.end(h)                      # close a span begun elsewhere
        with tr.span("execute"):       # and begin one here
            pass

    t = threading.Thread(target=work, name="svc-exec")
    t.start()
    t.join()
    _balanced(tr)
    # lanes are assigned at begin: the request span keeps the main
    # thread's lane, the execute span gets the worker's
    names = tr.thread_names()
    req, ex = tr.spans()
    assert names[req.tid] == "MainThread"
    assert names[ex.tid] == "svc-exec"


def test_enabled_span_lands_in_profiler_host_plane(tmp_path):
    """The annotation bridge: an enabled recorder's context span is a
    host-plane event of a profiler trace, under its name and with about
    its duration; the disabled recorder emits nothing there."""
    import jax

    tr = TraceRecorder(capacity=16)
    jax.profiler.start_trace(str(tmp_path))
    try:
        with tr.span("probe.enabled") as sp:
            time.sleep(0.05)
        with NULL_TRACE.span("probe.null"):
            time.sleep(0.01)
    finally:
        jax.profiler.stop_trace()
    (path,) = tmp_path.rglob("*.xplane.pb")
    pd = jax.profiler.ProfileData.from_file(str(path))
    host = [(e.name, e.duration_ns / 1e6) for p in pd.planes
            if p.name == "/host:CPU" for line in p.lines
            for e in line.events]
    (dur_ms,) = [d for n, d in host if n == "probe.enabled"]
    # at least the sleep it wraps, nested inside the span's clock reads
    assert 50.0 <= dur_ms <= sp.dur_ms
    assert not any(n == "probe.null" for n, _ in host)


# ----------------------------------------------------------- metrics --

def test_metrics_registry_counters_deterministic():
    m = MetricsRegistry()
    m.counter("b.two").inc()
    m.counter("a.one").inc(3)
    m.counter("b.two").inc()
    assert m.counters() == {"a.one": 3, "b.two": 2}
    assert list(m.counters()) == ["a.one", "b.two"]   # sorted


def test_disabled_registry_is_null():
    assert NULL_REGISTRY.counter("x") is NULL_REGISTRY.counter("y")
    NULL_REGISTRY.counter("x").inc()
    assert NULL_REGISTRY.counters() == {}
    assert NULL_REGISTRY.snapshot() == {"counters": {}}


def test_prometheus_text_cumulative():
    m = MetricsRegistry()
    m.counter("sched.ticks").inc(4)
    m.counter("sched.ticks").inc()
    m.counter("service.stalls")
    txt = export.prometheus_text(m)
    assert "# TYPE repro_sched_ticks counter\nrepro_sched_ticks 5" in txt
    assert "# TYPE repro_service_stalls counter\nrepro_service_stalls 0" \
        in txt


# -------------------------------------------- service-level balance --

def test_exec_thread_exception_ends_request_spans(small_system):
    server = _server(small_system)
    backend = EngineBackend(server)
    boom = RuntimeError("exec thread dies")
    backend.execute = lambda batch, pred: (_ for _ in ()).throw(boom)
    obs = Observability.create(capacity=256)
    svc = RetrievalService(backend, AdmissionConfig(max_batch=8,
                                                    pad_multiple=8),
                           obs=obs)
    svc.start()
    futs = svc.submit_many(list(small_system.queries.terms[:8]))
    svc.flush()
    with pytest.raises(RuntimeError):
        futs[0].result(timeout=30)
    svc.stop()
    _balanced(obs.trace)
    errs = [sp for sp in obs.trace.spans()
            if sp.name == "request" and (sp.attrs or {}).get("error")]
    assert len(errs) == 8
    assert all(e.attrs["error"] == "RuntimeError" for e in errs)


def test_stop_cancellation_balances_spans(small_system):
    server = _server(small_system)
    obs = Observability.create(capacity=256)
    svc = RetrievalService(
        EngineBackend(server),
        AdmissionConfig(max_batch=64, pad_multiple=8, max_wait_ms=1e6),
        obs=obs)
    # submit below max_batch with an enormous wait bound: the batch
    # never forms, stop(drain=False) must cancel and close every span
    futs = svc.submit_many(list(small_system.queries.terms[:4]),
                           deadline_ms=1e9)
    svc.stop(drain=False)
    assert all(f.cancelled() for f in futs)
    _balanced(obs.trace)
    cancelled = [sp for sp in obs.trace.spans()
                 if sp.name == "request"
                 and (sp.attrs or {}).get("cancelled")]
    assert len(cancelled) == 4
    assert obs.metrics.counters()["service.cancelled"] == 4


# ------------------------------------- instrumented serve: invariants --

def test_instrumented_serve_identical_and_hot_path_clean(small_system):
    server = _server(small_system)
    qt = small_system.queries.terms[:16]
    classes = np.asarray(server.predict_classes(qt))
    params = server.params_of(classes)
    ranked_ref, _ = server.engine.serve(qt, params)   # warm + reference

    obs = Observability.create(capacity=1024)
    server.engine.bind_obs(obs)
    # obs on, same shapes: zero new compiles, zero implicit transfers,
    # bit-identical rows
    with S.hot_path(server.engine):
        ranked, timings = server.engine.serve(qt, params)
    np.testing.assert_array_equal(np.asarray(ranked),
                                  np.asarray(ranked_ref))
    _balanced(obs.trace)
    stages = {sp.name for sp in obs.trace.spans()}
    assert {"engine.gather", "engine.rerank"} <= stages
    # the timings dict is derived from the spans — one per stage label
    assert set(timings) and all(v >= 0.0 for v in timings.values())
    assert obs.metrics.counters()["engine.compiles"] == 0


def test_deterministic_counters_xla_vs_kernel_interpret(small_system):
    """The committed counter surface is machine-independent: the same
    query stream through the XLA lowering and the Pallas interpret
    lowering (the REPRO_FORCE_KERNEL=1 routing) must count the same
    dispatches, retirements, and submissions."""
    qt = small_system.queries.terms[:24]

    def run(use_kernel):
        server = _server(small_system, "rho", use_kernel=use_kernel)
        obs = Observability.create(capacity=4096)
        backend = ContinuousBackend(server, slots=8, grain=4)
        svc = RetrievalService(backend,
                               AdmissionConfig(max_batch=8,
                                               pad_multiple=8),
                               obs=obs)
        backend.scheduler.warmup()
        out = svc.serve_all(list(qt), deadline_ms=1e9)
        svc.stop()
        _balanced(obs.trace)
        c = obs.metrics.counters()
        # timing-free subset: tick/batch counts depend on thread
        # interleaving, these do not
        keys = ("queue.submitted", "sched.retired.rho_exhausted",
                "sched.retired.stream_exhausted",
                "sched.retired.pool_complete", "service.cancelled")
        return out, {k: c[k] for k in keys}

    out_x, c_x = run(False)
    out_k, c_k = run(True)
    assert c_x == c_k
    assert sum(v for k, v in c_x.items() if k.startswith("sched.retired")) \
        == len(qt)
    for a, b in zip(out_x, out_k):
        np.testing.assert_array_equal(a["ranked"], b["ranked"])


def test_continuous_churn_trace_balanced_and_exports(small_system,
                                                     tmp_path):
    """A 40-query churn run: every tick window, slot occupancy, and
    per-stage span closes; the exported Chrome trace passes the schema
    check; attribution joins per-query and shared cost."""
    server = _server(small_system, "rho")
    obs = Observability.create(capacity=8192)
    backend = ContinuousBackend(server, slots=8, grain=4)
    svc = RetrievalService(backend,
                           AdmissionConfig(max_batch=8, pad_multiple=8),
                           telemetry=TelemetryBuffer(), obs=obs)
    backend.scheduler.warmup()
    results = svc.serve_all(list(small_system.queries.terms[:40]),
                            deadline_ms=1e9)
    svc.stop()
    _balanced(obs.trace)
    by_name = {}
    for sp in obs.trace.spans():
        by_name.setdefault(sp.name, []).append(sp)
    assert len(by_name["request"]) == len(by_name["queue"]) == 40
    assert len(by_name["slot"]) == 40
    # every working tick logged its window spans and t0 <= t1 holds
    assert len(by_name["tick"]) >= 1
    for sp in obs.trace.spans():
        assert sp.t1 >= sp.t0
    # per-slot spans carry the deterministic retire metadata
    for sp in by_name["slot"]:
        assert sp.attrs["retire_reason"] in ("rho_exhausted",
                                             "stream_exhausted",
                                             "pool_complete")
        assert 0.0 < sp.attrs["occupancy"] <= 1.0

    path = tmp_path / "trace.json"
    payload = export.write_chrome_trace(str(path), obs.trace)
    assert export.validate_chrome_trace(payload) == []
    assert json.loads(path.read_text())["traceEvents"]
    assert export.main([str(path)]) == 0

    # telemetry join: every record carries the trace_id its spans use
    recs = svc.telemetry.snapshot()
    assert recs and all(r.trace_id >= 0 for r in recs)
    rows = export.attribution_table(obs.trace, recs)
    assert len(rows) == len(recs)
    row = rows[0]
    assert {"request_ms", "queue_ms", "slot_ms"} <= set(row)
    att = export.latency_attribution(obs.trace, recs[0].trace_id)
    assert att["stages"]["request"] >= att["stages"]["queue"]


def test_trace_id_minus_one_outside_admission(small_system):
    server = _server(small_system)
    buf = TelemetryBuffer()
    out = server.serve_batch(small_system.queries.terms[:8])
    res = {"class": int(out["classes"][0]), "width": int(out["widths"][0]),
           "total_ms": 1.0, "queue_ms": 0.0, "service_ms": 1.0,
           "deadline_ms": 10.0, "deadline_met": True}
    buf.record(small_system.queries.terms[0], res, 0, 0.0)
    (rec,) = buf.snapshot()
    assert rec.trace_id == -1
    assert export.attribution_table(NULL_TRACE, [rec]) == []


# ------------------------------------------------------ stall watchdog --

def _hold_the_tick(seconds):
    time.sleep(seconds)


def test_held_tick_records_one_stall_naming_the_holder(small_system):
    """A tick held 0.7 s with work in flight is one stall: its span runs
    from the last working tick to the next, its stacks name the holding
    function on the tick thread, and service.stalls counts it."""
    from repro.serving.service import STALL_S

    qt = small_system.queries.terms
    server = _server(small_system, "rho")
    obs = Observability.create(capacity=4096)
    backend = ContinuousBackend(server, query_len=qt.shape[1], slots=8,
                                grain=4)
    svc = RetrievalService(backend,
                           AdmissionConfig(max_batch=8, pad_multiple=8),
                           obs=obs)
    backend.scheduler.warmup()
    prog = backend.scheduler.prog
    chunk, held = prog.chunk, []

    def held_chunk(*a):
        if not held:
            held.append(True)
            _hold_the_tick(0.7)
        return chunk(*a)

    svc.start()
    try:
        # first-call work (slot-table allocation) stays out of the held
        # stretch; the watchdog closes whatever it saw there
        svc.submit(qt[0], deadline_ms=1e9).result(timeout=60)
        time.sleep(0.2)
        before = obs.metrics.counters()["service.stalls"]
        t_held = time.perf_counter()
        prog.chunk = held_chunk
        futs = svc.submit_many(list(qt[1:9]), deadline_ms=1e9)
        for f in futs:
            f.result(timeout=60)
    finally:
        svc.stop()
    (stall,) = [sp for sp in obs.trace.spans()
                if sp.name == "stall" and sp.t1 > t_held]
    assert STALL_S * 1e3 <= stall.dur_ms < 5e3
    tick_stack = stall.attrs["stacks"]["svc-sched"]
    assert 0 < len(tick_stack) <= 8
    assert any(f.startswith("_hold_the_tick ") for f in tick_stack)
    assert list(stall.attrs["stacks"])[0] == "svc-sched"
    assert 0.0 <= stall.attrs["watchdog_lag_ms"] < STALL_S * 1e3
    assert stall.attrs["device_ready"] in (True, False)
    assert obs.metrics.counters()["service.stalls"] == before + 1


@pytest.mark.parametrize("on", [False, True])
def test_watchdog_thread_only_with_obs(small_system, on):
    server = _server(small_system, "rho")
    svc = RetrievalService(ContinuousBackend(server, slots=8, grain=4),
                           AdmissionConfig(max_batch=8, pad_multiple=8),
                           obs=Observability.create() if on else None)
    svc.start()
    try:
        names = [t.name for t in svc._threads]
    finally:
        svc.stop()
    assert "svc-sched" in names
    assert ("svc-watchdog" in names) is on
    assert ("service.stalls" in svc.obs.metrics.counters()) is on


# ------------------------------------------------------- null overhead --

def test_null_obs_records_nothing_through_service(small_system):
    server = _server(small_system)
    svc = RetrievalService(EngineBackend(server),
                           AdmissionConfig(max_batch=8, pad_multiple=8))
    out = svc.serve_all(list(small_system.queries.terms[:8]))
    svc.stop()
    assert len(out) == 8
    assert out[0]["service_ms"] > 0.0     # timings still derive obs-off
    assert svc.obs is NULL_OBS
    assert NULL_OBS.trace.counts()["n_held"] == 0
    assert NULL_OBS.metrics.counters() == {}


# ----------------------------------------------------- stats rendering --

def test_server_stats_stage_p99_rendering():
    st = server_lib.ServerStats(
        n_queries=4, latencies_ms=[1, 2, 3, 4], mean_param=10.0,
        class_histogram=np.zeros(3, np.int64), pct_in_envelope=None,
        stage_ms={"stage1_ms": {"mean": 1.25, "p99": 2.0, "n": 4},
                  "legacy_ms": 0.5})
    s = st.summary()
    assert "stage1=1.2ms(p99=2.0 n=4)" in s
    assert "legacy=0.5ms" in s            # bare-float producers render


def test_service_stats_stage_ms_has_p99(small_system):
    server = _server(small_system)
    svc = RetrievalService(EngineBackend(server),
                           AdmissionConfig(max_batch=8, pad_multiple=8))
    svc.serve_all(list(small_system.queries.terms[:16]))
    svc.stop()
    st = svc.stats()
    assert st.stage_ms
    for v in st.stage_ms.values():
        assert set(v) == {"mean", "p99", "n"} and v["n"] >= 1
        assert v["p99"] >= v["mean"] or np.isclose(v["p99"], v["mean"])
    st.summary()                          # renders without raising
