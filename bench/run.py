"""Run one benchmark cell once, on the chips of the machine it starts on.

    python3 bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

The cell (``BENCHMARK.json``) names a configuration and a traffic mix.
The run loads or builds the configuration's deployment, starts its
service, compiles every shape the window can use and serves a few
warm-up requests, then offers the traffic open-loop for ``--seconds``
seconds: one ``submit`` per arrival at its due time.  Set-up is
everything from process start to the first due arrival.  After the
window it reads the device's peak memory, frees the service and checks
every finished request against the configuration's plain reference.

The last line of standard output is one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics`` (the cell's end-to-end metrics,
or with ``--trace 1`` its per-layer ones, read from a profiler trace of
a steady stretch of the window and the program's spans), ``device``
(with ``busy_s`` and ``window_s`` when traced), ``breakdown`` when
traced, and last ``checks``: each number compared with its limit, which
also close standard error.  The run exits non-zero, printing no such
line, where JAX finds no TPU or fewer chips than the cell asks for.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import threading  # noqa: E402
from pathlib import Path  # noqa: E402

BENCH = Path(__file__).resolve().parent
REPO = BENCH.parent
sys.path[:0] = [str(BENCH), str(REPO / "src")]

from harness import spec as spec_lib  # noqa: E402

#: spans that last a request's lifetime, not a stretch of host work
_LIFETIME_SPANS = {"request", "slot", "queue"}


def _log(msg: str) -> None:
    print(f"[bench] {msg}", file=sys.stderr, flush=True)


def _use_compile_cache(root: Path) -> str:
    """JAX's persistent compilation cache at a fixed path inside the
    checkout, every program kept whatever its compile time or size; the
    TPU runtime's logs inside the checkout too, unless told otherwise.

    ``JAX_COMPILATION_CACHE_DIR`` is not followed here, as the program's
    ``launch/compile_cache.py`` follows it: a directory set for the whole
    machine would be shared by two checkouts measured against each other,
    and a benchmark run's cache belongs to its checkout alone."""
    os.environ.setdefault("TPU_LOG_DIR",
                          str(root / "bench" / ".cache" / "tpu_logs"))
    import jax
    path = str(root / "bench" / ".cache" / "jax")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return path


class _CompileCounter:
    """Counts every compile JAX makes: backend compiles and programs
    loaded from the persistent cache."""

    def __init__(self):
        import jax
        self.n = 0
        self._lock = threading.Lock()
        jax.monitoring.register_event_duration_secs_listener(self._dur)
        jax.monitoring.register_event_listener(self._event)

    def _dur(self, event, duration, **kw):
        if event == "/jax/core/compile/backend_compile_duration":
            with self._lock:
                self.n += 1

    def _event(self, event, **kw):
        if event == "/jax/compilation_cache/cache_hits":
            with self._lock:
                self.n += 1

    def close(self):
        import jax
        jax.monitoring.unregister_event_duration_listener(self._dur)
        jax.monitoring.unregister_event_listener(self._event)


class _Profiler:
    """Traces ``span`` seconds of the window from a thread of its own, in
    the middle of the window, marked by the ``trace.WINDOW`` annotation.
    The profiler starts ``MARGIN_S`` before the mark and stops as long
    after it: the trace records an op only whole, so an op that runs
    across an edge of the mark is then in the trace, clipped to it."""

    MARGIN_S = 1.0

    def __init__(self, out: Path, t_open: float, seconds: float,
                 span: float):
        self.out = out
        self.t0 = t_open + max(0.0, (seconds - span) / 2)
        self.span = min(span, seconds)
        self.perf_at_mark = None
        self.error = None
        self._thread = threading.Thread(target=self._run, name="bench-prof")

    def start(self):
        self._thread.start()

    def _run(self):
        import jax
        from harness.trace import WINDOW
        try:
            time.sleep(max(0.0, self.t0 - self.MARGIN_S
                           - time.perf_counter()))
            jax.profiler.start_trace(str(self.out))
            try:
                time.sleep(self.MARGIN_S)
                self.perf_at_mark = time.perf_counter()
                with jax.profiler.TraceAnnotation(WINDOW):
                    time.sleep(self.span)
                time.sleep(self.MARGIN_S)
            finally:
                jax.profiler.stop_trace()
        except Exception as e:     # noqa: BLE001 — reported by join()
            self.error = e

    def join(self):
        self._thread.join()
        if self.error is not None:
            raise self.error
        return sorted(self.out.rglob("*.xplane.pb"))[-1]


def _idle_gaps(summary, spans, perf_at_mark: float, top: int = 10):
    """The longest idle stretches of the chip, each named by the host span
    that covers most of it ("host_idle" where none does)."""
    from harness.trace import WINDOW
    marks = [h for h in summary.host if h[0] == WINDOW]
    offset = (marks[0][1] - perf_at_mark * 1e9) if marks else 0.0
    work = [(s.name, s.t0 * 1e9 + offset, s.t1 * 1e9 + offset)
            for s in spans if s.name not in _LIFETIME_SPANS and s.t1 >= 0]
    out = []
    for g0, g1 in sorted(summary.gaps, key=lambda g: g[0] - g[1])[:top]:
        best, cover = "host_idle", 0.0
        for name, s0, s1 in work:
            c = min(g1, s1) - max(g0, s0)
            if c > cover:
                best, cover = name, c
        out.append([best, (g1 - g0) / 1e9])
    return out


def _failed(outcomes, at_close: str) -> int:
    """Requests that failed, or never came though they were due: in a
    drained window every request is due an answer; in a cancelled one,
    every request due before the last one answered."""
    errs = sum(1 for o in outcomes if o.error is not None)
    missing = [o for o in outcomes if o.result is None and o.error is None]
    if at_close == "drain":
        return errs + len(missing)
    answered = [o.due for o in outcomes if o.result is not None]
    last = max(answered) if answered else float("-inf")
    return errs + sum(1 for o in missing if o.due <= last)


def main(argv=None, *, root: Path = REPO, platforms=("tpu",)) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    root = Path(root)
    cell = spec_lib.load_cell(root, args.workload)
    cfg, traffic = cell.config, cell.traffic

    cache_dir = _use_compile_cache(root)
    import jax
    devices = jax.devices()
    dev = devices[0]
    _log(f"platform={dev.platform} device_kind={dev.device_kind} "
         f"count={len(devices)} compile_cache={cache_dir}")
    if dev.platform not in platforms:
        _log(f"JAX found no TPU (platform {dev.platform!r}); this "
             "benchmark runs only on the chip")
        return 2
    if len(devices) < cell.chips:
        _log(f"cell {cell.name} needs {cell.chips} chips, found "
             f"{len(devices)}")
        return 2
    from harness import roofline
    pk = roofline.peaks(dev.device_kind) if dev.platform == "tpu" else None

    import numpy as np

    from harness import build, check, record, serve, traffic as traffic_lib
    counter = _CompileCounter()
    ref_mod = check.load_reference(root, cfg["reference"])
    dep = build.load_deployment(cfg, root / "bench" / ".cache", ref_mod,
                                log=_log)
    obs = None
    if args.trace:
        from repro.obs import Observability
        obs = Observability.create(capacity=1 << 20)
    server = serve.make_server(dep)
    svc = serve.make_service(dep, server, obs)
    svc.start()
    warm = dep.train_terms[:2 * cfg["serving"]["max_batch"]]
    n_warm = serve.warm_up(svc, dep, warm)
    schedule = traffic_lib.make_schedule(
        traffic, args.seconds, args.seed, dep.freq, dep.train_terms,
        cfg["query_law"])
    if obs is not None:
        obs.trace.clear()
    prof = None
    t_open = time.perf_counter() + 0.05
    if args.trace:
        out = root / "bench" / ".cache" / "trace" / cell.name
        shutil.rmtree(out, ignore_errors=True)
        prof = _Profiler(out, t_open, args.seconds,
                         float(traffic["trace_seconds"]))
        prof.start()
    setup_s = t_open + float(schedule.due[0]) - T_START
    _log(f"set-up {setup_s:.1f} s ({n_warm} shapes warmed); window of "
         f"{len(schedule.due)} requests over {args.seconds} s")
    c0 = counter.n
    outcomes = serve.run_window(svc, schedule, t_open, args.seconds,
                                at_close=traffic["at_close"])
    window_compiles = counter.n - c0
    counter.close()
    t_stop = time.perf_counter()
    xplane = prof.join() if prof is not None else None
    peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
               for d in devices[:cell.chips])
    spans = list(obs.trace.spans()) if obs is not None else []
    counters = obs.metrics.counters() if obs is not None else {}
    del svc, server
    gc.collect()

    failed = _failed(outcomes, traffic["at_close"])
    done = [o for o in outcomes if o.result is not None]
    t_ref = time.perf_counter()
    queries = np.stack([q for q, o in zip(schedule.queries, outcomes)
                        if o.result is not None]) if done else \
        np.zeros((0, schedule.queries.shape[1]), np.int32)
    ref = ref_mod.Reference(dep.collection, cfg["serving"], dep.glob,
                           queries if len(queries) else schedule.queries)
    numbers, in_env = check.check(
        ref, queries,
        noise_ids=[serve.noise_id(o.result) for o in done],
        served_class=[o.result["class"] for o in done],
        served_width=[o.result["width"] for o in done],
        served_lists=[o.result["ranked"] for o in done],
        node_params=dep.forest, max_budget=max(dep.cutoffs),
        rbp_p=cfg["training_log"]["rbp_p"], tau=cfg["training_log"]["tau"])
    _log(f"reference over {len(done)} requests: "
         f"{time.perf_counter() - t_ref:.1f} s")
    limits = cfg["limits"]
    correct = (failed == 0 and bool(done)
               and all(numbers[k] is not None and numbers[k] <= limits[k]
                       for k in check.NUMBERS))

    summary = None
    if xplane is not None:
        from harness.trace import reduce_trace
        summary = reduce_trace(str(xplane), n_chips=cell.chips)
    run = record.Run(cell=cell.name, config=cfg, traffic=traffic,
                     seconds=args.seconds, t_open=t_open, t_stop=t_stop,
                     setup_s=setup_s,
                     outcomes=outcomes, window_compiles=window_compiles,
                     in_envelope_pct=in_env, spans=spans,
                     counters=counters, trace=summary, peaks=pk)
    metrics = {}
    for m in (cell.per_layer if args.trace else cell.end_to_end):
        v = spec_lib.load_reader(root, m["name"])(run)
        if v is not None:
            metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices), "memory_peak_bytes": int(peak)}
    result = {"correct": bool(correct), "attempted": len(outcomes),
              "failed": int(failed), "metrics": metrics, "device": device}
    if summary is not None:
        device["busy_s"] = summary.busy_s
        device["window_s"] = summary.window_s
        ops = sorted(summary.op_seconds().items(), key=lambda kv: -kv[1])
        result["breakdown"] = {
            "device_ops": [[k, v] for k, v in ops[:10]],
            "idle_gaps": _idle_gaps(summary, spans, prof.perf_at_mark)}
    checks = {k: {"value": numbers[k], "limit": limits[k]}
              for k in check.NUMBERS}
    checks["failed"] = {"value": failed, "limit": 0}
    result["checks"] = checks
    for k, v in checks.items():
        _log(f"check {k} = {v['value']} (limit {v['limit']})")
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
