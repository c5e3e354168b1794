"""Knee sweep: the highest offered rate a configuration sustains.

    python3 bench/sweep.py --config msmarco-rho --rates 80,90,100 --seconds 20

Builds (or loads) the deployment once, then offers each rate open-loop for
``--seconds`` seconds through a fresh service over the same server, and
prints one JSON line per rate: requests offered, answered inside the
window, the backlog (due but unanswered) sampled across the window, and
the median and 95th percentile of due-to-result time of the answered
requests.  A rate is sustained when the backlog's median over the last
quarter of the window exceeds its median over the second quarter by
less than ``GROWTH`` of the requests that arrive in half the window: a
rate 5% over capacity grows it by about 5%.  The sweep stops at the
first rate not sustained and prints the knee, the highest rate
sustained.  Run once, when a cell is defined; the cell's traffic file
then holds its rate as a number.
"""

from __future__ import annotations

import argparse
import json
import statistics
import sys
import time
from pathlib import Path

BENCH = Path(__file__).resolve().parent
sys.path[:0] = [str(BENCH), str(BENCH.parent / "src")]

from run import _use_compile_cache  # noqa: E402

GROWTH = 0.05
SAMPLES = 40


def backlog(outcomes, t: float) -> int:
    return sum(1 for o in outcomes if o.due <= t
               and not (o.result is not None and o.done <= t))


def sustained(samples: list, rate: float, seconds: float) -> bool:
    """Backlog ``samples`` taken evenly over the window: compare the
    median of the last quarter with the median of the second.  Medians,
    so that a stall of a few seconds, whose backlog drains again, does not
    read as growth."""
    q = len(samples) // 4
    mid = statistics.median(samples[q:2 * q])
    end = statistics.median(samples[-q:])
    return end - mid < GROWTH * rate * seconds / 2


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--config", required=True)
    ap.add_argument("--rates", required=True, help="comma-separated q/s")
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--seed", type=int, default=2718281828)
    args = ap.parse_args(argv)
    root = BENCH.parent
    _use_compile_cache(root)
    import jax

    from harness import build, check, record, serve, traffic as traffic_lib
    dev = jax.devices()[0]
    print(json.dumps({"platform": dev.platform, "kind": dev.device_kind,
                      "count": len(jax.devices())}), flush=True)
    cfg = json.loads((BENCH / "configs" / f"{args.config}.json").read_text())
    t0 = time.perf_counter()
    dep = build.load_deployment(cfg, BENCH / ".cache",
                                check.load_reference(root, cfg["reference"]),
                                log=lambda m: print(m, flush=True))
    t1 = time.perf_counter()
    server = serve.make_server(dep)
    svc = serve.make_service(dep, server)
    svc.start()
    n = serve.warm_up(svc, dep, dep.train_terms[:128])
    svc.stop()
    print(json.dumps({"config": args.config, "deployment_s": t1 - t0,
                      "warm_s": time.perf_counter() - t1, "shapes": n,
                      "hits": dep.hits, "phase_s": dep.seconds}), flush=True)
    knee = None
    for i, rate in enumerate(float(r) for r in args.rates.split(",")):
        sched = traffic_lib.make_schedule(
            {"law": "poisson", "rate_qps": rate, "deadline_ms": 1e4},
            args.seconds, args.seed + i, dep.freq, dep.train_terms,
            cfg["query_law"])
        svc = serve.make_service(dep, server)
        svc.start()
        c0 = server.engine.n_compiles
        t_open = time.perf_counter() + 0.05
        outs = serve.run_window(svc, sched, t_open, args.seconds,
                                at_close="cancel")
        lat = [(o.done - o.due) * 1e3 for o in outs if o.result is not None]
        samples = [backlog(outs, t_open + args.seconds * (j + 1) / SAMPLES)
                   for j in range(SAMPLES)]
        ok = sustained(samples, rate, args.seconds)
        print(json.dumps({
            "config": args.config, "rate": rate, "offered": len(outs),
            "answered_in_window": sum(
                1 for o in outs if o.result is not None
                and o.done <= t_open + args.seconds),
            "backlog": samples, "sustained": ok,
            "p50_ms": statistics.median(lat) if lat else None,
            "p95_ms": record.nearest_rank(lat, 0.95) if lat else None,
            "gen_lag_p95_ms": record.nearest_rank(
                [(o.sent - o.due) * 1e3 for o in outs], 0.95),
            "errors": sum(1 for o in outs if o.error is not None),
            "compiles": server.engine.n_compiles - c0}), flush=True)
        if not ok:
            break
        knee = rate
    print(json.dumps({"config": args.config, "knee_qps": knee}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
