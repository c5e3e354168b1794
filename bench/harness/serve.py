"""The system under test, reached only through its serving surface, and
the open loop that drives it.

The service is what ``launch/serve.py::build_service`` builds over the
configuration's backend: ``ContinuousBackend`` (the slot scheduler) or
``EngineBackend`` (batch-once).  The window submits one request per
arrival at its due time and times it from that due time until its future
resolves, so a stall delays every request due behind it.
"""

from __future__ import annotations

import dataclasses
import threading
import time

import numpy as np

__all__ = ["Outcome", "make_server", "make_service", "noise_id",
           "run_window", "warm_up"]


def _row_tagged(base):
    """``base`` (``EngineBackend``) with each result tagged by its row in
    the padded batch: batch-once keys stage-2 noise on that row."""

    class RowTagged(base):
        def execute(self, qt, pred):
            results, timings = super().execute(qt, pred)
            for i, r in enumerate(results):
                r["row"] = i
            return results, timings

    return RowTagged


def noise_id(result: dict) -> int:
    """The id stage 2 keyed its noise on when it served ``result``: the
    batch row (batch-once) or the request's arrival index (continuous)."""
    return int(result["row"] if "row" in result else result["trace_id"])


def make_server(dep):
    """The program's ``RetrievalServer`` for the deployment, serving the
    cascade fitted here (``harness.cascade``)."""
    from repro.core.cascade import Cascade
    from repro.serving import pipeline as sp

    s = dep.config["serving"]
    forest = dep.config["training_log"]["forest"]
    casc = Cascade(kind="forest", nodes=[],
                   node_params=[dict(p) for p in dep.forest],
                   max_depth=int(forest["max_depth"]),
                   n_cutoffs=len(dep.cutoffs))
    return sp.RetrievalServer(dep.index, casc, sp.ServingConfig(
        knob=s["knob"], cutoffs=dep.cutoffs, threshold=s["threshold"],
        rerank_depth=s["rerank_depth"], stream_cap=s["stream_cap"]))


def make_service(dep, server, obs=None):
    """A ``RetrievalService`` over ``server`` with the configuration's
    lifecycle, as ``launch/serve.py::build_service`` builds it."""
    from repro.launch.serve import build_service
    from repro.serving.service import ContinuousBackend, EngineBackend

    s = dep.config["serving"]
    qlen = dep.config["query_law"]["max_len"]
    if s["lifecycle"] == "continuous":
        backend = ContinuousBackend(server, query_len=qlen,
                                    slots=s["slots"])
    elif s["lifecycle"] == "batch-once":
        backend = _row_tagged(EngineBackend)(server, query_len=qlen)
    else:
        raise ValueError(f"unknown lifecycle {s['lifecycle']!r}")
    return build_service(backend, batch=s["max_batch"], deadline_ms=1e3,
                         census="", obs=obs)


def warm_up(svc, dep, queries: np.ndarray) -> int:
    """Compile every shape the window can use and serve ``queries`` once
    through the started service, so first-call work lands in set-up.
    Returns the fresh executables compiled."""
    s = dep.config["serving"]
    if s["lifecycle"] == "continuous":
        sizes = [s["slots"]]
    else:   # every padded batch the admission queue can form
        m = svc.backend.pad_multiple
        sizes = list(range(m, s["max_batch"] + 1, m))
    n = svc.warmup_now(sizes)
    if svc.warmup.failed:
        raise RuntimeError(f"warm-up failed for padded batches "
                           f"{sorted(svc.warmup.failed)}")
    for f in [svc.submit(q, deadline_ms=1e6) for q in queries]:
        f.result(timeout=600)
    return n


@dataclasses.dataclass
class Outcome:
    due: float                 # perf_counter seconds
    sent: float = float("nan")
    done: float = float("nan")
    result: dict | None = None
    error: str | None = None
    cancelled: bool = False


def _resolve(out: Outcome, fut) -> None:
    out.done = time.perf_counter()
    if fut.cancelled():
        out.cancelled = True
        return
    err = fut.exception()
    if err is not None:
        out.error = repr(err)
    else:
        out.result = fut.result()


def run_window(svc, schedule, t_open: float, seconds: float, *,
               at_close: str, drain_s: float = 60.0) -> list[Outcome]:
    """Submit ``schedule`` open-loop from ``t_open`` (perf_counter) on.

    At ``t_open + seconds`` the window closes.  ``at_close="drain"`` then
    waits up to ``drain_s`` for every request to resolve; ``"cancel"``
    stops the service and cancels what is still queued (over capacity by
    design), after the batches in flight have resolved."""
    outs = [Outcome(due=t_open + d) for d in schedule.due]
    futs = []
    for q, out in zip(schedule.queries, outs):
        left = out.due - time.perf_counter()
        if left > 0:
            time.sleep(left)
        out.sent = time.perf_counter()
        fut = svc.submit(q, deadline_ms=schedule.deadline_ms)
        fut.add_done_callback(lambda f, o=out: _resolve(o, f))
        futs.append(fut)
    time.sleep(max(0.0, t_open + seconds - time.perf_counter()))
    if at_close == "drain":
        done = threading.Event()
        left = [len(futs)]
        lock = threading.Lock()

        def _count(_f):
            with lock:
                left[0] -= 1
                if left[0] == 0:
                    done.set()

        for f in futs:
            f.add_done_callback(_count)
        done.wait(drain_s)
        svc.stop(drain=False)
    elif at_close == "cancel":
        svc.stop(drain=False)
    else:
        raise ValueError(f"unknown at_close {at_close!r}")
    for f in futs:
        if not f.done():
            f.cancel()
    return outs
