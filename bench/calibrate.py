"""Readings that the limits of ``correct`` are set from.

    python3 bench/calibrate.py --workload rho-steady --seeds 1,2,3 \
        --control-seeds 1,2,3 --seconds 10

For each seed, in one process: the cell's traffic, at the cell's rate,
through a fresh service over one server, for ``--seconds`` seconds; then
the numbers ``correct`` compares (``harness.check``) for the program's
answers.  For each control seed the same requests are also answered by
the control — the plain reference computed in bfloat16, the precision
below the float32 the configuration states — and compared the same way.
One JSON line per seed and source.  The benchmark's own runs never run
the control.
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

BENCH = Path(__file__).resolve().parent
sys.path[:0] = [str(BENCH), str(BENCH.parent / "src")]

from run import _use_compile_cache  # noqa: E402


def control_answers(ctrl, queries, noise_ids, node_params):
    """The control's classes, parameters and lists for ``queries``."""
    cls = ctrl.classes(queries, node_params)
    widths = [ctrl.width(c) for c in cls]
    lists = [ctrl.query(q, int(n), [w])["lists"][w]
             for q, n, w in zip(queries, noise_ids, widths)]
    return cls, widths, lists


def readings(root: Path, workload: str, seeds, control_seeds=(),
             seconds: float = 10.0, log=print):
    """Yield one dict per seed and source: the numbers ``correct``
    compares, for the program's answers and, on ``control_seeds``, for
    the control's."""
    import ml_dtypes
    import numpy as np

    from harness import build, check, serve, spec, traffic as traffic_lib
    cell = spec.load_cell(root, workload)
    cfg, traffic = cell.config, cell.traffic
    ref_mod = check.load_reference(root, cfg["reference"])
    dep = build.load_deployment(cfg, root / "bench" / ".cache", ref_mod,
                                log=log)
    server = serve.make_server(dep)
    glob, node_params = dep.glob, dep.forest
    kw = dict(node_params=node_params, max_budget=max(dep.cutoffs),
              rbp_p=cfg["training_log"]["rbp_p"],
              tau=cfg["training_log"]["tau"])
    controls = {int(s) for s in control_seeds}
    warmed = False
    for seed in (int(s) for s in seeds):
        sched = traffic_lib.make_schedule(traffic, seconds, seed, dep.freq,
                                          dep.train_terms, cfg["query_law"])
        svc = serve.make_service(dep, server)
        svc.start()
        if not warmed:
            serve.warm_up(svc, dep, dep.train_terms[:128])
            warmed = True
        t_open = time.perf_counter() + 0.05
        outs = serve.run_window(svc, sched, t_open, seconds,
                                at_close=traffic["at_close"])
        done = [(q, o.result) for q, o in zip(sched.queries, outs)
                if o.result is not None]
        queries = np.stack([q for q, _ in done])
        ids = [serve.noise_id(r) for _, r in done]
        t0 = time.perf_counter()
        ref = ref_mod.Reference(dep.collection, cfg["serving"], glob,
                                queries)
        numbers, env = check.check(
            ref, queries, noise_ids=ids,
            served_class=[r["class"] for _, r in done],
            served_width=[r["width"] for _, r in done],
            served_lists=[r["ranked"] for _, r in done], **kw)
        yield {"workload": cell.name, "seed": seed, "source": "program",
               "answered": len(done), "offered": len(outs),
               "reference_s": time.perf_counter() - t0,
               "in_envelope_pct": env, **numbers}
        if seed in controls:
            t0 = time.perf_counter()
            ctrl = ref_mod.Reference(dep.collection, cfg["serving"], glob,
                                     queries, dtype=ml_dtypes.bfloat16)
            cls, widths, lists = control_answers(ctrl, queries, ids,
                                                 node_params)
            numbers, env = check.check(
                ref, queries, noise_ids=ids, served_class=cls,
                served_width=widths, served_lists=lists, **kw)
            yield {"workload": cell.name, "seed": seed,
                   "source": "control_bf16", "answered": len(done),
                   "control_s": time.perf_counter() - t0,
                   "in_envelope_pct": env, **numbers}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--seconds", type=float, default=10.0)
    args = ap.parse_args(argv)
    root = BENCH.parent
    _use_compile_cache(root)
    for row in readings(root, args.workload, args.seeds.split(","),
                        [s for s in args.control_seeds.split(",") if s],
                        args.seconds, log=lambda m: print(m, flush=True)):
        print(json.dumps(row), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
