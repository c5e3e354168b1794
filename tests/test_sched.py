"""Continuous-batching scheduler: churn bit-identity against the
batch-once engine on every predicted-class bucket, O(1) compiles across
admit/retire churn, class co-grouping, deadline/cancel accounting, and
the retirement telemetry trail."""

import math

import numpy as np
import pytest

from repro.analysis import sanitizers as S
from repro.core import experiment as E
from repro.obs import Observability
from repro.online.telemetry import TelemetryBuffer
from repro.serving import pipeline as serve_lib
from repro.serving.service import ContinuousBackend, RetrievalService


@pytest.fixture(scope="module")
def small_system():
    return E.build_system(E.ExperimentConfig(
        n_docs=400, vocab=900, n_queries=40, stream_cap=128,
        pool_depth=100, gold_depth=50, query_batch=16, seed=21))


def _hash_rows(qt):
    # classes as a pure function of query *content*: the scheduler's
    # refill groups differ from batch-once groups, so a batch-position
    # stub (test_service.py's idiom) would not survive regrouping
    qt = np.asarray(qt)
    return np.where(qt >= 0, qt, 0).sum(axis=1) + (qt >= 0).sum(axis=1)


def _server(sys_, knob="rho", class_shift=None, **cfg_kw):
    cuts = sys_.k_cutoffs if knob == "k" else sys_.rho_cutoffs
    cfg = serve_lib.ServingConfig(
        knob=knob, cutoffs=cuts, rerank_depth=30,
        stream_cap=sys_.cfg.stream_cap, **cfg_kw)
    server = serve_lib.RetrievalServer(sys_.index, None, cfg)
    n_cls = len(cuts) + 1
    shift = class_shift if class_shift is not None else {"v": 0}
    real = server.predict_classes

    def stub(qt, knob=None):
        if knob not in (None, cfg.knob):      # depth etc.: real registry
            return real(qt, knob=knob)
        return ((_hash_rows(qt) + shift["v"]) % n_cls).astype(np.int64)

    server.predict_classes = stub
    return server, shift


def _drain(svc):
    while svc.outstanding:
        if not svc.step():
            raise RuntimeError("scheduler idle with work outstanding")


# ------------------------------------------------- churn bit-identity --

def _with_shared_docs(sys_, qt):
    """``qt`` plus one query whose terms share documents: the two terms
    with the longest postings lists, the first of them twice."""
    a, b = np.argsort(-np.diff(sys_.index.offsets), kind="stable")[:2]
    row = np.full((1, qt.shape[1]), -1, qt.dtype)
    row[0, :3] = (a, b, a)
    return np.concatenate([qt, row])


@pytest.mark.parametrize("knob", ["rho", "k"])
def test_churn_bit_identity_every_bucket(small_system, knob):
    """Results under slot churn are bit-identical to one batch-once
    ``engine.serve`` of the same stream, and both to the per-bucket
    reference with its dense stage 2 — with every class bucket of the
    cutoff grid represented in the mix and a query whose terms share
    documents."""
    server, _ = _server(small_system, knob)
    qt = _with_shared_docs(small_system, small_system.queries.terms[:40])
    classes = np.asarray(server.predict_classes(qt))
    n_cls = len(server.cfg.cutoffs) + 1
    assert set(classes.tolist()) == set(range(n_cls))  # all buckets hit
    ranked_ref, _ = server.engine.serve(qt, server.params_of(classes))
    np.testing.assert_array_equal(
        ranked_ref, server.serve_batch_reference(qt)["ranked"])

    backend = ContinuousBackend(server, slots=16, grain=4, window=8)
    svc = RetrievalService(backend)
    out = svc.serve_all(list(qt), deadline_ms=1e6)
    for i, res in enumerate(out):
        np.testing.assert_array_equal(res["ranked"], ranked_ref[i])
        assert res["class"] == classes[i]
        assert res["chunks_executed"] <= res["chunks_max"]
        assert 0.0 < res["slot_occupancy"] <= 1.0
    sch = backend.scheduler.stats()
    assert sch["n_admitted"] == sch["n_retired"] == len(qt)
    if knob == "rho":
        assert set(sch["retire_reasons"]) <= {"rho_exhausted",
                                              "stream_exhausted"}
    else:
        assert set(sch["retire_reasons"]) == {"pool_complete"}


@pytest.fixture(scope="module")
def wide_system():
    """A stream cap of 512 and k cutoffs up to 512: pools wider than the
    Pallas top-k's 128 per block, as at the k deployment's 10,000."""
    return E.build_system(E.ExperimentConfig(
        n_docs=2000, vocab=900, n_queries=40, stream_cap=512,
        pool_depth=512, gold_depth=50, query_batch=16, seed=23))


def _streams(sys_, qt):
    """Per query, its stream's length and the documents of positive
    stage-1 score in it (the most a pool can hold)."""
    from repro.retrieval import jass
    idx = sys_.index
    ds, im = jass.gather_streams(idx.offsets, idx.postings_doc,
                                 idx.postings_impact.astype(np.float32),
                                 qt, cap=sys_.cfg.stream_cap)
    slen, live = [], []
    for d, m in zip(np.asarray(ds), np.asarray(im)):
        score = np.zeros(sys_.cfg.n_docs)
        np.add.at(score, d[d >= 0], m[d >= 0])
        slen.append(int((d >= 0).sum()))
        live.append(int((score > 0).sum()))
    return np.asarray(slen), np.asarray(live)


@pytest.mark.parametrize("use_kernel", [False, True],
                         ids=["xla", "kernel"])
def test_wide_k_pool_churn_bit_identity(wide_system, use_kernel):
    """The k knob with a stream cap at or above its largest cutoff and
    cutoffs past 128: under churn at mixed predicted classes, every
    served list is bit-identical to the per-bucket reference, including
    queries whose pool holds more than 128 live documents (the "xla"
    pool-select route that pools wider than the Pallas top-k take)."""
    sys_ = wide_system
    server, _ = _server(sys_, "k", use_kernel=use_kernel)
    assert sys_.cfg.stream_cap >= max(server.cfg.cutoffs) > 128
    # the base queries plus rows of the longest postings lists
    qt = sys_.queries.terms[:32]
    top = np.argsort(-np.diff(sys_.index.offsets), kind="stable")[:12]
    wide = np.full((6, qt.shape[1]), -1, qt.dtype)
    for i in range(6):
        wide[i, :2] = top[2 * i: 2 * i + 2]
    qt = np.concatenate([qt, wide])
    classes = np.asarray(server.predict_classes(qt))
    widths = np.asarray(server.params_of(classes))
    slen, live = _streams(sys_, qt)
    assert len(set(widths.tolist())) > 3              # mixed classes
    assert (np.minimum(widths, live) > 128).any()
    ref = server.serve_batch_reference(qt)["ranked"]

    obs = Observability.create(capacity=4096)
    backend = ContinuousBackend(server, slots=16, grain=4, window=8)
    out = RetrievalService(backend, obs=obs).serve_all(list(qt),
                                                       deadline_ms=1e6)
    assert server.engine.topk_routes["finalize"] == "xla"
    chunk_p = backend.scheduler.prog.chunk_p
    for i, res in enumerate(out):
        np.testing.assert_array_equal(res["ranked"], ref[i])
        assert res["width"] == widths[i]
        # k cuts no stage-1 work: every slot runs its whole stream
        assert res["chunks_executed"] == math.ceil(slen[i] / chunk_p)
    c = obs.metrics.counters()
    assert c["sched.finalized"] == len(qt)
    assert c["sched.k_width"] == int(widths.sum())
    assert c["sched.chunk_ticks"] == sum(r["chunks_executed"] for r in out)


@pytest.mark.parametrize("n", [1, 7])
def test_ragged_tail_bit_identity(small_system, n):
    """Trickle traffic (below a refill grain / not a grain multiple)
    pads within the fixed shapes and stays bit-identical."""
    server, _ = _server(small_system, "rho")
    qt = small_system.queries.terms[:n]
    classes = np.asarray(server.predict_classes(qt))
    ranked_ref, _ = server.engine.serve(qt, server.params_of(classes))
    backend = ContinuousBackend(server, slots=8, grain=4)
    svc = RetrievalService(backend)
    out = svc.serve_all(list(qt), deadline_ms=1e6)
    for i, res in enumerate(out):
        np.testing.assert_array_equal(res["ranked"], ranked_ref[i])


def test_mid_flight_hot_swap_bit_identity(small_system):
    """A predictor swap while slots are in flight: admitted requests
    keep their admission-time widths, later admissions see the new
    predictor — and every result stays bit-identical to a batch-once
    serve at the widths actually used."""
    shift = {"v": 0}
    server, _ = _server(small_system, "rho", class_shift=shift)
    backend = ContinuousBackend(server, slots=8, grain=4, window=8)
    svc = RetrievalService(backend)
    qt = small_system.queries.terms[:24]

    futs = svc.submit_many(list(qt[:12]), deadline_ms=1e6)
    svc.flush()
    # tick until some (not all) of the first wave resolved: mid-flight
    while sum(f.done() for f in futs) < 4:
        assert svc.step()
    assert svc.outstanding > 0
    # a stubbed swap: predict_classes is already a stand-in (no cascade
    # was built), so flip its weights-equivalent and bump the version
    # the way swap_predictor would
    shift["v"] = 2
    server.predictor_version += 1
    futs += svc.submit_many(list(qt[12:]), deadline_ms=1e6)
    svc.flush()
    _drain(svc)

    out = [f.result() for f in futs]
    versions = {res["predictor_version"] for res in out}
    assert len(versions) == 2           # both predictors served traffic
    widths = np.asarray([res["width"] for res in out], np.int64)
    ranked_ref, _ = server.engine.serve(qt, widths)
    for i, res in enumerate(out):
        np.testing.assert_array_equal(res["ranked"], ranked_ref[i])


# ------------------------------------------- depth knob under churn --

def _depth_server(sys_, knob):
    """Continuous-scheduler server with the depth knob live, depth
    classes stubbed as a pure function of query content (same idiom as
    the primary-knob stub — survives regrouping)."""
    from repro.core import knobs as knobs_lib
    pool = 30 if knob == "rho" else int(max(sys_.k_cutoffs))
    server, _ = _server(sys_, knob,
                        depth_cutoffs=knobs_lib.depth_cutoffs(pool))
    grid = server.cfg.depth_cutoffs

    def pdepth(qt):
        cls = (_hash_rows(qt) % (len(grid) + 1)).astype(np.int64)
        return cls, server.params_of(cls, knob="depth")

    server.predict_depths = pdepth
    return server, pdepth


@pytest.mark.parametrize("knob", ["rho", "k"])
def test_mixed_depth_churn_bit_identity(small_system, knob):
    """Per-slot retirement at each query's predicted depth under churn
    is bit-identical to one batch-once serve with the same depth vector
    — and the stage-2 row accounting is the deterministic counter the
    bench diffs."""
    server, pdepth = _depth_server(small_system, knob)
    qt = small_system.queries.terms[:40]
    classes = np.asarray(server.predict_classes(qt))
    dcls, depths = pdepth(qt)
    assert len(set(depths.tolist())) > 1           # genuinely mixed
    ranked_ref, _ = server.engine.serve(qt, server.params_of(classes),
                                        depth_vec=depths)

    backend = ContinuousBackend(server, slots=16, grain=4, window=8)
    svc = RetrievalService(backend)
    out = svc.serve_all(list(qt), deadline_ms=1e6)
    for i, res in enumerate(out):
        np.testing.assert_array_equal(res["ranked"], ranked_ref[i])
        assert res["depth"] == depths[i]
        assert res["depth_class"] == dcls[i]
    sch = backend.scheduler.stats()
    widths = np.asarray(server.params_of(classes))
    rows, full = server._rows_scored(widths, depths)
    assert sch["n_rows_scored"] == int(rows.sum())
    assert sch["n_rows_full"] == int(full.sum())
    assert sch["n_rows_scored"] < sch["n_rows_full"]   # real savings


def test_mixed_depth_churn_compiles_nothing(small_system):
    """Depth churn acceptance: after warmup, admit/retire cycles with
    per-query depths spanning the whole grid compile zero executables
    (the depth vector is traced, like rho/k)."""
    server, _ = _depth_server(small_system, "rho")
    L = small_system.queries.terms.shape[1]
    backend = ContinuousBackend(server, query_len=L, slots=8, grain=4)
    svc = RetrievalService(backend)
    assert backend.scheduler.warmup() > 0
    rng = np.random.default_rng(11)
    qpool = small_system.queries.terms
    with S.compile_sentinel(server.engine):
        for cycle in range(12):
            n = 1 + cycle % 8
            rows = qpool[rng.integers(0, qpool.shape[0], n)]
            svc.serve_all(list(rows), deadline_ms=1e6)
    sch = backend.scheduler.stats()
    assert sch["n_retired"] == sum(1 + c % 8 for c in range(12))
    assert sch["n_rows_scored"] <= sch["n_rows_full"]


def test_depth_pinned_to_max_matches_depth_free_scheduler(small_system):
    """A depth server whose every prediction is the full pool retires
    bit-identically to a scheduler with no depth knob at all."""
    from repro.core import knobs as knobs_lib
    server, _ = _server(small_system, "rho")
    deep, _ = _server(small_system, "rho",
                      depth_cutoffs=knobs_lib.depth_cutoffs(30))
    # no stub: with no depth cascade, predict_depths answers the
    # no-envelope class -> full pool for every query
    qt = small_system.queries.terms[:24]
    a = RetrievalService(
        ContinuousBackend(server, slots=8, grain=4)).serve_all(
        list(qt), deadline_ms=1e6)
    b_backend = ContinuousBackend(deep, slots=8, grain=4)
    b = RetrievalService(b_backend).serve_all(list(qt), deadline_ms=1e6)
    for ra, rb in zip(a, b):
        np.testing.assert_array_equal(ra["ranked"], rb["ranked"])
        assert rb["depth"] == deep.cfg.depth_pool_width
    sch = b_backend.scheduler.stats()
    assert sch["n_rows_scored"] == sch["n_rows_full"]  # no-op mask


# ------------------------------------------------------- O(1) compiles --

def test_zero_compiles_across_50_churn_cycles(small_system):
    """50 admit/retire cycles with mixed batch sizes compile nothing
    after warmup: the four scheduler programs are the whole executable
    surface, whatever the churn pattern."""
    server, _ = _server(small_system, "rho")
    engine = server.engine
    L = small_system.queries.terms.shape[1]
    backend = ContinuousBackend(server, query_len=L, slots=8, grain=4)
    svc = RetrievalService(backend)
    assert backend.scheduler.warmup() > 0      # cold start compiles
    rng = np.random.default_rng(7)
    qpool = small_system.queries.terms
    with S.compile_sentinel(engine):
        for cycle in range(50):
            n = 1 + cycle % 8                  # 1..8, every tail shape
            rows = qpool[rng.integers(0, qpool.shape[0], n)]
            svc.serve_all(list(rows), deadline_ms=1e6)
    sch = backend.scheduler.stats()
    assert sch["n_admitted"] == sch["n_retired"] == sum(
        1 + c % 8 for c in range(50))


# --------------------------------------------------------- co-grouping --

def test_co_grouping_selects_nearest_classes(small_system):
    server, _ = _server(small_system, "rho")
    backend = ContinuousBackend(server, slots=8, grain=4)
    svc = RetrievalService(backend)
    sched = backend.scheduler
    cand = list(range(5))               # only len() matters to _select
    classes = np.array([3, 0, 3, 1, 3])
    keep, back = sched._select(cand, classes, 3)
    # head (most urgent) always ships; seats go to its class neighbors
    assert keep.tolist() == [0, 2, 4] and back.tolist() == [1, 3]
    sched.co_group = False
    keep, back = sched._select(cand, classes, 3)
    assert keep.tolist() == [0, 1, 2]   # urgency order, no regrouping
    del svc


def test_grain_must_fit_slot_table(small_system):
    server, _ = _server(small_system, "rho")
    backend = ContinuousBackend(server, slots=4, grain=8)
    with pytest.raises(ValueError, match="grain"):
        RetrievalService(backend)


def test_overlong_query_fails_fast(small_system):
    server, _ = _server(small_system, "rho")
    L = small_system.queries.terms.shape[1]
    backend = ContinuousBackend(server, query_len=L, slots=8, grain=4)
    svc = RetrievalService(backend)
    fut = svc.submit(np.zeros(L + 3, np.int32), deadline_ms=1e6)
    svc.flush()
    while not fut.done():
        svc.step()
    with pytest.raises(ValueError, match="query length"):
        fut.result()


# ------------------------------------------------ deadline accounting --

def test_deadline_tally_counts_served_requests(small_system):
    server, _ = _server(small_system, "rho")
    backend = ContinuousBackend(server, slots=8, grain=4)
    svc = RetrievalService(backend)
    ok = svc.serve_all(list(small_system.queries.terms[:4]),
                       deadline_ms=1e6)
    late = svc.serve_all(list(small_system.queries.terms[4:8]),
                         deadline_ms=0.0)     # expired on arrival
    assert all(res["deadline_met"] for res in ok)
    assert not any(res["deadline_met"] for res in late)
    st = svc.stats()
    assert st.n_deadline_met == 4 and st.n_deadline_missed == 4
    assert st.deadline_met == pytest.approx(0.5)
    assert "deadline_met=50.0%" in st.summary()


def test_cancelled_requests_are_not_deadline_misses(small_system):
    """stop(drain=False) with work queued and mid-flight: every future
    resolves (cancel or result), and cancels never pollute the
    deadline-met fraction."""
    server, _ = _server(small_system, "rho")
    backend = ContinuousBackend(server, slots=8, grain=4)
    svc = RetrievalService(backend)
    futs = svc.submit_many(list(small_system.queries.terms[:10]),
                           deadline_ms=1e6)
    svc.flush()
    svc.step()                          # admit a grain: some mid-flight
    svc.stop(drain=False)
    assert all(f.done() for f in futs)
    n_cancelled = sum(f.cancelled() for f in futs)
    assert n_cancelled > 0
    st = svc.stats()
    assert st.n_cancelled == n_cancelled
    served = 10 - n_cancelled
    assert (st.n_deadline_met or 0) + (st.n_deadline_missed or 0) == served
    if served == 0:
        assert math.isnan(st.deadline_met)
    else:
        assert st.deadline_met == 1.0   # generous deadlines: all met
    assert f"cancelled={n_cancelled}" in st.summary()


# ----------------------------------------------- retirement telemetry --

def test_retirement_trail_reaches_telemetry_ring(small_system):
    server, _ = _server(small_system, "rho")
    backend = ContinuousBackend(server, slots=8, grain=4)
    buf = TelemetryBuffer(capacity=64)
    svc = RetrievalService(backend, telemetry=buf)
    svc.serve_all(list(small_system.queries.terms[:8]), deadline_ms=1e6)
    recs = buf.snapshot()
    assert len(recs) == 8
    for r in recs:
        assert r.retire_reason in ("rho_exhausted", "stream_exhausted")
        assert 0 <= r.chunks_executed <= r.chunks_max
        assert 0.0 < r.slot_occupancy <= 1.0
        assert r.pred_class >= 0 and r.ranked is not None


def test_admission_stamped_when_request_leaves_pending(small_system):
    """Under churn every continuous result has queue_ms >= 0: admission
    is stamped when the refill takes the request from the pending set,
    not at the start of the tick that admits it.  Requests arriving
    while a tick finalizes are taken by that tick's refill, which is
    where the tick-start stamp read below zero."""
    server, _ = _server(small_system)
    backend = ContinuousBackend(server, slots=8, grain=4)
    svc = RetrievalService(backend)
    backend.scheduler.warmup()
    prog = backend.scheduler.prog
    finalize = prog.finalize
    qt = list(small_system.queries.terms)
    futs = svc.submit_many(qt[:8], deadline_ms=1e9)
    waiting = qt[8:40]

    def finalize_amid_arrivals(*a):
        out = finalize(*a)
        if waiting:                    # a wave lands mid-tick
            futs.extend(svc.submit_many(waiting[:4], deadline_ms=1e9))
            del waiting[:4]
        return out

    prog.finalize = finalize_amid_arrivals
    _drain(svc)
    results = [f.result() for f in futs]
    assert len(results) == 40
    for r in results:
        assert r["queue_ms"] >= 0.0, r["queue_ms"]
        assert r["total_ms"] >= r["queue_ms"] + r["service_ms"] - 1e-6
