"""Single-dispatch serving engine: equivalence with the per-bucket
reference path, constant compile count as class diversity grows, and
scatter_back/padding round-trips."""

import numpy as np
import pytest

from repro.analysis import sanitizers
from repro.core import experiment as E
from repro.serving import bucketing
from repro.serving import pipeline as serve_lib


@pytest.fixture(scope="module")
def small_system():
    return E.build_system(E.ExperimentConfig(
        n_docs=400, vocab=900, n_queries=40, stream_cap=128,
        pool_depth=100, gold_depth=50, query_batch=16, seed=21))


def _server(sys_, knob, cutoffs, **cfg_kw):
    """Server with a stubbed predictor — engine behavior is independent of
    how classes are produced, so tests control them directly."""
    cfg = serve_lib.ServingConfig(
        knob=knob, cutoffs=cutoffs, rerank_depth=30,
        stream_cap=sys_.cfg.stream_cap, **cfg_kw)
    return serve_lib.RetrievalServer(sys_.index, None, cfg)


def _stub_classes(server, classes):
    real = server.predict_classes

    def stub(qt, knob=None, c=np.asarray(classes)):
        # stub the primary knob only; secondary knobs (depth) keep the
        # real registry behavior (no cascade -> no-envelope class)
        return c if knob in (None, server.cfg.knob) else real(qt, knob=knob)

    server.predict_classes = stub


# ------------------------------------------------------- equivalence (a) --

@pytest.mark.parametrize("knob", ["k", "rho"])
def test_single_dispatch_bit_identical_to_reference(small_system, knob):
    sys_ = small_system
    cuts = sys_.k_cutoffs if knob == "k" else sys_.rho_cutoffs
    server = _server(sys_, knob, cuts)
    n = 20                               # deliberately not a pad multiple
    classes = np.arange(n) % (len(cuts) + 1)   # every bucket live
    _stub_classes(server, classes)
    qt = sys_.queries.terms[:n]
    server.serve_batch(qt)               # warm the executable cache
    with sanitizers.no_transfers():      # steady state: no implicit h2d
        dyn = server.serve_batch(qt)
    ref = server.serve_batch_reference(qt)
    np.testing.assert_array_equal(dyn["ranked"], ref["ranked"])
    np.testing.assert_array_equal(dyn["widths"], ref["widths"])
    assert dyn["mean_param"] == ref["mean_param"]


def test_fixed_path_matches_reference_single_bucket(small_system):
    """serve_fixed == the reference path with every query in one bucket."""
    sys_ = small_system
    server = _server(sys_, "k", sys_.k_cutoffs)
    _stub_classes(server, np.full(16, 2))
    qt = sys_.queries.terms[:16]
    fixed = server.serve_fixed(qt, int(sys_.k_cutoffs[2]))
    ref = server.serve_batch_reference(qt)
    np.testing.assert_array_equal(fixed["ranked"], ref["ranked"])


# ------------------------------------------------------ compile count (b) --

def test_compile_count_constant_in_class_diversity(small_system):
    sys_ = small_system
    cuts = sys_.k_cutoffs
    server = _server(sys_, "k", cuts)
    qt = sys_.queries.terms[:24]
    _stub_classes(server, np.zeros(24, np.int64))
    server.serve_batch(qt)               # compile for this padded shape
    base = server.engine.n_compiles
    assert base > 0
    with sanitizers.hot_path(server.engine):   # no recompiles, no
        for n_distinct in (1, 2, 4, len(cuts) + 1):  # implicit transfers
            _stub_classes(server, np.arange(24) % n_distinct)
            out = server.serve_batch(qt)
            assert out["n_compiles"] == base, (
                f"recompiled at {n_distinct} distinct classes")
        # the fixed baseline rides the same executables
        server.serve_fixed(qt, int(cuts[-1]))
    assert server.engine.n_compiles == base


def test_warmup_precompiles_pad_grid(small_system):
    sys_ = small_system
    server = _server(sys_, "k", sys_.k_cutoffs)
    qlen = sys_.queries.terms.shape[1]
    compiled = server.engine.warmup([8, 16, 24], qlen)
    assert compiled == server.engine.n_compiles > 0
    before = server.engine.n_compiles
    for n in (5, 8, 13, 16, 23):         # all land on warmed shapes
        _stub_classes(server, np.arange(n) % 3)
        server.serve_batch(sys_.queries.terms[:n])
    assert server.engine.n_compiles == before


# ----------------------------------------------- scatter_back/padding (c) --

def test_scatter_back_round_trips_under_padding():
    rng = np.random.default_rng(0)
    n, depth, n_classes, pad_multiple = 37, 5, 4, 8
    classes = rng.integers(0, n_classes + 1, n)
    ranked = rng.integers(0, 1000, (n, depth)).astype(np.int32)
    buckets = bucketing.bucketize(classes, n_classes, pad_multiple)
    assert all(len(b["pad_idx"]) % pad_multiple == 0
               for b in buckets.values())
    per_bucket = {c: ranked[b["pad_idx"]] for c, b in buckets.items()}
    out = bucketing.scatter_back(n, buckets, per_bucket)
    np.testing.assert_array_equal(out, ranked)


def test_pad_rows_grid_and_inertness():
    a = np.arange(10, dtype=np.int32).reshape(5, 2)
    p = bucketing.pad_rows(a, 8, fill=-1)
    assert p.shape == (8, 2)
    np.testing.assert_array_equal(p[:5], a)
    assert (p[5:] == -1).all()
    assert bucketing.pad_rows(p, 8, fill=-1) is p      # already on grid
    assert bucketing.pad_length(0, 8) == 0
    assert bucketing.pad_length(9, 8) == 16


# ------------------------------------------------------ kernel path (d) --
# use_kernel=True + interpret=True executes the Pallas bodies on CPU —
# the same routing REPRO_FORCE_KERNEL=1 turns on in CI.

def _kernel_server(sys_, knob, cutoffs):
    cfg = serve_lib.ServingConfig(
        knob=knob, cutoffs=cutoffs, rerank_depth=30,
        stream_cap=sys_.cfg.stream_cap, use_kernel=True,
        kernel_block_p=32, kernel_block_d=64)  # real grids at test scale
    return serve_lib.RetrievalServer(sys_.index, None, cfg)


@pytest.mark.parametrize("knob", ["k", "rho"])
def test_kernel_path_bit_identical_to_oracle(small_system, knob):
    """Traced-rho impact_scan + blocked top-k through ServingEngine.serve
    match the jnp oracle engine for every bucket mix — including the
    per-bucket reference path."""
    sys_ = small_system
    cuts = sys_.k_cutoffs if knob == "k" else sys_.rho_cutoffs
    oracle = _server(sys_, knob, cuts)
    kern = _kernel_server(sys_, knob, cuts)
    n = 20
    classes = np.arange(n) % (len(cuts) + 1)   # every bucket live
    for server in (oracle, kern):
        _stub_classes(server, classes)
    qt = sys_.queries.terms[:n]
    oracle.serve_batch(qt)               # warm both executable caches
    kern.serve_batch(qt)
    with sanitizers.no_transfers():      # steady state: no implicit h2d
        a = oracle.serve_batch(qt)
        b = kern.serve_batch(qt)
    np.testing.assert_array_equal(a["ranked"], b["ranked"])
    np.testing.assert_array_equal(a["widths"], b["widths"])
    ref = kern.serve_batch_reference(qt)
    np.testing.assert_array_equal(b["ranked"], ref["ranked"])


@pytest.mark.parametrize("param", ["zero", "max"])
def test_kernel_path_rho_extremes(small_system, param):
    """rho=0 (nothing scored -> empty lists) and rho=P (everything
    scored) agree between kernel and oracle engines."""
    sys_ = small_system
    oracle = _server(sys_, "rho", sys_.rho_cutoffs)
    kern = _kernel_server(sys_, "rho", sys_.rho_cutoffs)
    qt = sys_.queries.terms[:16]
    rho = 0 if param == "zero" else sys_.cfg.stream_cap
    a = oracle.serve_fixed(qt, rho)
    b = kern.serve_fixed(qt, rho)
    np.testing.assert_array_equal(a["ranked"], b["ranked"])
    if param == "zero":
        assert (a["ranked"] == -1).all()


def test_kernel_path_compile_count_constant(small_system):
    """Acceptance: n_compiles stays O(1) under mixed per-query rho on the
    kernel path — the traced-rho kernel serves every bucket from one
    executable."""
    sys_ = small_system
    cuts = sys_.rho_cutoffs
    server = _kernel_server(sys_, "rho", cuts)
    qt = sys_.queries.terms[:24]
    _stub_classes(server, np.zeros(24, np.int64))
    server.serve_batch(qt)
    base = server.engine.n_compiles
    assert base > 0
    with sanitizers.hot_path(server.engine):
        for n_distinct in (2, 4, len(cuts) + 1):
            _stub_classes(server, np.arange(24) % n_distinct)
            out = server.serve_batch(qt)
            assert out["n_compiles"] == base, (
                f"kernel path recompiled at "
                f"{n_distinct} distinct rho classes")


def test_force_kernel_env(small_system, monkeypatch):
    """REPRO_FORCE_KERNEL=1 flips the auto-detect default (the CI leg
    that executes Pallas bodies on every PR); explicit use_kernel wins."""
    from repro.serving.engine import ServingEngine

    cfg = serve_lib.ServingConfig(
        knob="rho", cutoffs=small_system.rho_cutoffs, rerank_depth=30,
        stream_cap=small_system.cfg.stream_cap)
    monkeypatch.delenv("REPRO_FORCE_KERNEL", raising=False)
    assert ServingEngine(small_system.index, cfg).use_kernel is False
    monkeypatch.setenv("REPRO_FORCE_KERNEL", "1")
    eng = ServingEngine(small_system.index, cfg)
    assert eng.use_kernel is True and eng.interpret is True
    assert ServingEngine(small_system.index, cfg,
                         use_kernel=False).use_kernel is False


# ------------------------------------------------- depth knob (tentpole) --

def _depth_server(sys_, knob, cuts, *, kernel=False):
    """Server with the depth knob declared (grid over the candidate
    pool) but no depth cascade — predict_depths returns the full pool
    width for every query, the traced mask's no-op setting."""
    from repro.core import knobs as knobs_lib
    kw = dict(use_kernel=True, kernel_block_p=32,
              kernel_block_d=64) if kernel else {}
    pool = 30 if knob == "rho" else int(max(cuts))
    cfg = serve_lib.ServingConfig(
        knob=knob, cutoffs=cuts, rerank_depth=30,
        stream_cap=sys_.cfg.stream_cap,
        depth_cutoffs=knobs_lib.depth_cutoffs(pool), **kw)
    return serve_lib.RetrievalServer(sys_.index, None, cfg)


@pytest.mark.parametrize("kernel", [False, True],
                         ids=["oracle", "kernel"])
@pytest.mark.parametrize("knob", ["k", "rho"])
def test_depth_pinned_to_max_bit_identical(small_system, knob, kernel):
    """Acceptance: depth pinned to the pool width is bit-identical to a
    depth-free server on every rho/k bucket, on both engine paths."""
    sys_ = small_system
    cuts = sys_.k_cutoffs if knob == "k" else sys_.rho_cutoffs
    plain = (_kernel_server if kernel else
             lambda s, kn, c: _server(s, kn, c))(sys_, knob, cuts)
    deep = _depth_server(sys_, knob, cuts, kernel=kernel)
    n = 20
    classes = np.arange(n) % (len(cuts) + 1)       # every bucket live
    for server in (plain, deep):
        _stub_classes(server, classes)
    qt = sys_.queries.terms[:n]
    a = plain.serve_batch(qt)
    b = deep.serve_batch(qt)                       # rerank_dyn path
    assert (b["depths"] == deep.cfg.depth_pool_width).all()
    np.testing.assert_array_equal(a["ranked"], b["ranked"])
    np.testing.assert_array_equal(a["widths"], b["widths"])
    # full pool admitted -> the work accounting reports no savings
    assert b["stage2_rows_scored"] == b["stage2_rows_full"]


def test_depth_mask_equals_narrower_pool_on_k(small_system):
    """On the k knob the depth mask keeps the rank-ordered prefix of the
    shared pool — bit-identical to serving with a pool of that width
    (same candidates, same stage-2 scores, same rerank)."""
    sys_ = small_system
    server = _depth_server(sys_, "k", sys_.k_cutoffs)
    qt = sys_.queries.terms[:16]
    ref_p = int(max(sys_.k_cutoffs))
    d = server.cfg.depth_cutoffs[1]
    masked = server.serve_fixed(qt, ref_p, depth=d)["ranked"]
    narrow = server.serve_fixed(qt, d)["ranked"]
    np.testing.assert_array_equal(masked, narrow)
    if d < server.cfg.rerank_depth:
        assert (masked[:, d:] == -1).all()


def test_depth_truncates_the_scored_prefix_on_rho(small_system):
    """On the rho knob the full run ranks the whole pool, so a shallow
    depth's docs are a prefix-sized subset of it, -1 past d."""
    sys_ = small_system
    server = _depth_server(sys_, "rho", sys_.rho_cutoffs)
    qt = sys_.queries.terms[:16]
    ref_p = sys_.cfg.stream_cap
    full = server.serve_fixed(qt, ref_p)["ranked"]
    d = server.cfg.depth_cutoffs[0]
    shallow = server.serve_fixed(qt, ref_p, depth=d)["ranked"]
    assert (shallow[:, d:] == -1).all()
    for i in range(16):
        got = set(shallow[i][shallow[i] >= 0].tolist())
        assert got <= set(full[i][full[i] >= 0].tolist())
        assert len(got) == min(d, int((full[i] >= 0).sum()))


def test_depth_adds_one_executable_then_stays_compiled(small_system):
    """The rerank_dyn variant costs one extra executable per padded
    shape; mixed per-query depths after that compile nothing."""
    sys_ = small_system
    server = _depth_server(sys_, "k", sys_.k_cutoffs)
    qt = sys_.queries.terms[:16]
    _stub_classes(server, np.arange(16) % 3)
    server.serve_batch(qt)                         # warm (depth path)
    base = server.engine.n_compiles
    rng = np.random.default_rng(0)
    grid = np.asarray(server.cfg.depth_cutoffs)
    with sanitizers.hot_path(server.engine):
        for _ in range(3):
            dvec = grid[rng.integers(0, len(grid), 16)]
            out, _ = server.engine.serve(
                qt, server.params_of(np.arange(16) % 3),
                depth_vec=dvec)
            assert (out != -2).all()
    assert server.engine.n_compiles == base


# --------------------------------------------- explicit ranked pad (sat) --

def test_ranked_pad_is_explicit_sentinel(small_system):
    """A fixed param below rerank_depth yields a pool narrower than the
    final list: the tail is the explicit -1 no-document sentinel (the
    same value rerank_pool emits for exhausted pools), not an implicit
    clamp."""
    from repro.serving.engine import _pad_ranked
    a = np.arange(6, dtype=np.int32).reshape(2, 3)
    p = _pad_ranked(a, 5)
    np.testing.assert_array_equal(p[:, :3], a)
    assert p.shape == (2, 5) and (p[:, 3:] == -1).all()
    assert _pad_ranked(a, 3) is a                  # wide enough: no-op
    sys_ = small_system
    server = _server(sys_, "k", sys_.k_cutoffs)
    out = server.serve_fixed(sys_.queries.terms[:8], 5)["ranked"]
    assert out.shape == (8, server.cfg.rerank_depth)
    assert (out[:, 5:] == -1).all()
    assert (out[:, :5] >= 0).all()


# ------------------------------------------- config validation (sat) --

def test_config_rejects_rerank_depth_beyond_pool(small_system):
    with pytest.raises(ValueError, match="rerank_depth"):
        serve_lib.ServingConfig(
            knob="k", cutoffs=(10, 20, 40), rerank_depth=50,
            stream_cap=small_system.cfg.stream_cap)


def test_config_rejects_depth_grid_not_ending_at_pool(small_system):
    with pytest.raises(ValueError, match="depth"):
        serve_lib.ServingConfig(
            knob="k", cutoffs=(10, 20, 40), rerank_depth=30,
            stream_cap=small_system.cfg.stream_cap,
            depth_cutoffs=(5, 10, 20))             # pool is 40
    with pytest.raises(ValueError, match="depth"):
        serve_lib.ServingConfig(
            knob="rho", cutoffs=(8, 16, 32), rerank_depth=30,
            stream_cap=small_system.cfg.stream_cap,
            depth_cutoffs=(5, 10, 40))             # pool is 30


# --------------------------------------------------------------- timings --

def test_serve_batch_reports_stage_timings(small_system):
    sys_ = small_system
    server = _server(sys_, "rho", sys_.rho_cutoffs)
    _stub_classes(server, np.arange(8) % 3)
    out = server.serve_batch(sys_.queries.terms[:8])
    t = out["timings"]
    for key in ("predict_ms", "gather_ms", "stage1_ms", "stage2_ms",
                "rerank_ms", "total_ms"):
        assert key in t and t[key] >= 0.0
    assert t["total_ms"] >= t["gather_ms"]


# ---------------------------------------------------------- module names --

def _module_name(exe) -> str:
    """The HLO module name an executable was compiled under."""
    return exe.as_text().split(None, 2)[1].rstrip(",")


@pytest.mark.parametrize("knob", ["rho", "k"])
def test_every_program_lowers_under_a_stable_module_name(small_system,
                                                         knob):
    """Device traces name programs by module: every engine stage,
    scheduler program and cascade predict lowers as jit_<scope>_<stage>,
    never as the anonymous jit__unknown of a bare functools.partial."""
    from repro.core import cascade as cascade_lib
    from repro.serving.engine import SchedPrograms

    sys_ = small_system
    cuts = sys_.k_cutoffs if knob == "k" else sys_.rho_cutoffs
    labels = np.arange(sys_.queries.n_queries) % (len(cuts) + 1)
    casc = cascade_lib.train_cascade(
        sys_.features, labels, n_cutoffs=len(cuts),
        forest_kwargs=dict(n_trees=2, max_depth=2))
    server = serve_lib.RetrievalServer(sys_.index, casc,
                                       serve_lib.ServingConfig(
                                           knob=knob, cutoffs=cuts,
                                           rerank_depth=30,
                                           stream_cap=sys_.cfg.stream_cap))
    qt = sys_.queries.terms[:8]
    server.serve_batch(qt)
    if knob == "k":                      # a pool wider than the grid
        server.serve_fixed(qt, server.engine.max_k + 8)
    SchedPrograms.for_engine(server.engine, grain=8).warmup(
        8, qt.shape[1])
    names = {key[0]: _module_name(exe)
             for key, exe in server.engine._cache.items()}
    stages = {"gather", "stage1", "stage2", "rerank"}
    expect = {**{s: f"jit_engine_{s}" for s in stages},
              **{p: f"jit_sched_{p}"
                 for p in ("sgather", "refill", "chunk", "finalize")}}
    if knob == "k":                      # the k stage 1 is keyed by width
        del expect["stage1"]
        for w in (server.engine.max_k, server.engine.max_k + 8):
            expect[f"stage1:{w}"] = "jit_engine_stage1"
    assert names == expect

    node_params, thresholds = server._live[knob]
    args = (node_params, thresholds,
            np.full((8, qt.shape[1]), -1, np.int32),
            (server.stats, server.ctf, server.df))
    for fns, prefix in ((server._predict_fns, "cascade"),
                        (server._margin_fns, "margin")):
        text = fns[knob].lower(*args).as_text()
        assert text.startswith(f"module @jit_{prefix}_{knob} "), \
            text.splitlines()[0]
