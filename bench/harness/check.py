"""The comparison that decides ``correct``, and the effectiveness guard.

Every request the window finished is checked against the configuration's
plain reference (``bench/configs/<reference>.py``) under the id that
stage 2 keyed its noise on.  Three numbers are compared, each with the
limit the configuration states:

* ``class_miss_pct``: share of requests whose served class is not the
  class the reference's cascade evaluation gives, or whose served
  parameter is not that class's cutoff;
* ``list_miss_pct``: share of requests whose served list does not hold
  the same documents as the reference's list at the served parameter —
  stage 1 and pool selection;
* ``order_gap_max``: over requests, the widest inversion of the served
  order under the reference's stage-2 scores — stage 2 and rerank.

A request that failed or never came makes the run not correct whatever
the numbers say.  ``in_envelope_pct`` is the share of finished requests
whose list lies within the MED-RBP envelope tau of the same request's
reference list at the largest cutoff.
"""

from __future__ import annotations

import importlib.util
import sys
from pathlib import Path

import numpy as np

__all__ = ["load_reference", "med_rbp", "check", "NUMBERS"]

NUMBERS = ("class_miss_pct", "list_miss_pct", "order_gap_max")


def load_reference(root: Path, name: str):
    """The reference module ``bench/configs/<name>.py``."""
    path = root / "bench" / "configs" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(f"bench_ref_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[spec.name] = mod
    spec.loader.exec_module(mod)
    return mod


def med_rbp(a: np.ndarray, b: np.ndarray, p: float) -> float:
    """MED under rank-biased precision of two -1-padded ranked lists: the
    largest RBP difference any relevance assignment can make (Tan and
    Clarke), with weights (1 - p) p**i."""
    def side(x, y):
        wy = {int(d): (1.0 - p) * p ** i for i, d in enumerate(y) if d >= 0}
        return sum(max(0.0, (1.0 - p) * p ** i - wy.get(int(d), 0.0))
                   for i, d in enumerate(x) if d >= 0)
    return max(side(a, b), side(b, a))


def _order_gap(served: np.ndarray, s2: np.ndarray) -> float:
    """Widest amount by which a served doc outscores one served above it."""
    if len(served) < 2:
        return 0.0
    run_min = np.minimum.accumulate(s2)
    return float(max(0.0, np.max(s2[1:] - run_min[:-1])))


def check(ref, requests, *, noise_ids, served_class, served_width,
          served_lists, node_params, max_budget, rbp_p: float, tau: float):
    """Compare served classes, parameters and lists with ``ref`` (a
    reference instance) request by request.  Returns (numbers,
    in_envelope_pct)."""
    n = len(requests)
    if n == 0:
        return {k: None for k in NUMBERS}, None
    ref_cls = ref.classes(requests, node_params)
    miss, list_miss, gap, inside = 0, 0, 0.0, 0
    for i in range(n):
        c = int(served_class[i])
        w = int(served_width[i])
        if c != int(ref_cls[i]) or w != ref.width(c):
            miss += 1
        budgets = sorted({w, int(max_budget)})
        got = ref.query(requests[i], int(noise_ids[i]), budgets)
        want = got["lists"][w]
        served = np.asarray(served_lists[i], np.int64)
        if set(served[served >= 0].tolist()) != set(want[want >= 0].tolist()):
            list_miss += 1
        docs = served[served >= 0]
        gap = max(gap, _order_gap(docs, got["stage2"](docs)
                                  .astype(np.float64)))
        if med_rbp(served, got["lists"][int(max_budget)], rbp_p) <= tau:
            inside += 1
    numbers = {"class_miss_pct": 100.0 * miss / n,
               "list_miss_pct": 100.0 * list_miss / n,
               "order_gap_max": gap}
    return numbers, 100.0 * inside / n
