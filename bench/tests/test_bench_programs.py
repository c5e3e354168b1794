"""The readers of the program's own names: module runs read from a
recorded chip trace, and each per-layer reader of them, of the host
annotations and of the program's spans, on runs built by hand — and
each finds nothing to read in a run of a program without them."""

from __future__ import annotations

import shutil
import types

import pytest

from bench_tiny import BENCH, REPO

from harness import programs, record, spec, trace

TINY_TRACE = BENCH / "tests" / "data" / "tiny_v5e.xplane.pb"
TICK_STEPS = ("tick.finalize", "tick.refill", "tick.chunk")


def _run(summary=None, spans=(), counters=None, cell="x"):
    return record.Run(cell=cell, config={}, traffic={}, seconds=10.0,
                      t_open=100.0, t_stop=111.0, setup_s=1.0, outcomes=[],
                      window_compiles=0, in_envelope_pct=None,
                      spans=list(spans), counters=dict(counters or {}),
                      trace=summary)


def _span(name, t0, t1):
    return types.SimpleNamespace(name=name, t0=t0, t1=t1)


def _summary(host=(), gaps=(), modules=None):
    s = trace.TraceSummary(window_ns=(0.0, 1e9), n_chips=1, busy_ns=0.0,
                           ops=[], gaps=list(gaps), host=list(host))
    if modules is not None:
        s.modules = [programs.Module(*m) for m in modules]
    return s


def _read(name, run):
    return spec.load_reader(REPO, name)(run)


def test_modules_read_from_a_recorded_chip_trace():
    every = programs.read_modules(TINY_TRACE, (float("-inf"),
                                               float("inf")))
    # recorded on a TPU v5 lite from two anonymous lambdas, twice each
    assert [r.name for r in every] == ["jit__lambda"] * 4
    assert [r.dur_ns for r in every] == [64206.0, 172441.0, 64203.0,
                                         172197.0]
    t = trace.reduce_trace(str(TINY_TRACE))
    inside = programs.read_modules(TINY_TRACE, t.window_ns)
    assert inside == every[2:]            # the pair in "bench.window"
    # the summary the other readers use is as it was
    assert t.window_ns == (42120410.0, 45082090.0)
    assert t.busy_ns == 236090.0 and len(t.gaps) == 25


def test_module_runs_found_where_the_run_left_its_trace(tmp_path):
    t = trace.reduce_trace(str(TINY_TRACE))
    run = _run(t, cell="rho-steady")
    assert programs.module_runs(run, tmp_path) is None      # no file
    dst = tmp_path / ".cache" / "trace" / "rho-steady" / "plugins"
    dst.mkdir(parents=True)
    shutil.copy(TINY_TRACE, dst / "host.xplane.pb")
    assert len(programs.module_runs(run, tmp_path)) == 2
    assert programs.module_runs(_run(None), tmp_path) is None


def test_device_ms_readers_by_hand():
    mods = [("jit_sched_sgather", 10, 2e6), ("jit_sched_refill", 20, 1e6),
            ("jit_sched_sgather", 30, 4e6), ("jit_sched_refill", 40, 1e6),
            ("jit_sched_chunk", 50, 3e6), ("jit_sched_chunk", 60, 5e6),
            ("jit_sched_finalize", 70, 9e6), ("jit_cascade_rho", 80, 7e6)]
    run = _run(_summary(modules=mods))
    assert _read("refill_dev_ms.tail", run) == pytest.approx(4.0)
    assert _read("chunk_dev_ms.tail", run) == pytest.approx(4.0)
    assert _read("finalize_dev_ms.tail", run) == pytest.approx(9.0)
    # a program whose modules carry no names: nothing to read
    anon = _run(_summary(modules=[("jit__unknown", 0, 1e6)]))
    for name in ("refill_dev_ms.tail", "chunk_dev_ms.tail",
                 "finalize_dev_ms.tail"):
        assert _read(name, anon) is None
        assert _read(name, _run(None)) is None


def test_idle_in_ticks_by_hand():
    gaps = [(0, 10), (20, 40), (50, 60), (90, 100)]        # 50 ns idle
    host = [("tick.refill", 5, 25), ("tick.chunk", 22, 30),
            ("predict", 50, 60), ("tick.finalize", 95, 200),
            ("bench.window", 0, 1000)]
    # inside the steps: 5 of the first gap, 10 of the second, 5 of the
    # last; the predict annotation is not a step
    run = _run(_summary(host=host, gaps=gaps))
    assert _read("idle_in_ticks_pct.tail", run) == pytest.approx(40.0)
    assert programs.idle_share_in(_summary(host=host, gaps=gaps),
                                  TICK_STEPS) == pytest.approx(40.0)
    bare = _run(_summary(host=[("bench.window", 0, 1000)], gaps=gaps))
    assert _read("idle_in_ticks_pct.tail", bare) is None
    assert _read("idle_in_ticks_pct.tail", _run(None)) is None


def test_tick_sync_by_hand():
    spans = [_span("tick", 100.0, 100.2), _span("tick", 100.3, 100.5),
             _span("sched.sync", 100.01, 100.03),
             _span("sched.sync", 100.1, 100.11),
             _span("tick.refill", 100.0, 100.1)]
    assert _read("tick_sync_ms.tail", _run(spans=spans)) == \
        pytest.approx(15.0)
    assert _read("tick_sync_ms.tail", _run(spans=spans[:2])) is None


def test_stall_seconds_by_hand():
    spans = [_span("stall", 99.0, 101.5),        # 1.5 s in the window
             _span("stall", 105.0, 105.75),
             _span("tick", 100.0, 100.2)]
    run = _run(spans=spans, counters={"service.stalls": 2})
    assert _read("stall_s.tail", run) == pytest.approx(2.25)
    quiet = _run(spans=spans[2:], counters={"service.stalls": 0})
    assert _read("stall_s.tail", quiet) == 0.0
    # a service with no watchdog has no such counter
    assert _read("stall_s.tail", _run(spans=spans)) is None
