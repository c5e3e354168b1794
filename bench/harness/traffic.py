"""Open-loop arrival schedules, made from a traffic file and a seed.

A traffic file states the arrival law and its numbers; this module is the
one generator that reads every such file.  Laws:

* ``poisson``: ``rate_qps`` requests per second.  The count in a window
  of ``seconds`` is fixed at ``round(rate_qps * seconds)``; the first
  request is due as the window opens and the gaps between the others are
  exponential draws scaled to a mean of ``1 / rate_qps``: a Poisson
  process given its count.

The work is the same for every seed: the gaps and the queries are drawn
once from the file's ``work_seed`` (0 where it names none), and
``--seed`` puts the gaps in an order of its own.  So two seeds send the
same queries in the same order, and so under the same request ids, which
key stage 2's noise, as bursty as each other but with the bursts in
other places.  Every request carries ``deadline_ms``.  The queries are a
held-out pool (``data.query_pool``): no query repeats in a window and
none is one of the cascade's training queries.
"""

from __future__ import annotations

import dataclasses

import numpy as np

from harness import data

__all__ = ["Schedule", "make_schedule"]

LAWS = ("poisson",)


@dataclasses.dataclass
class Schedule:
    due: np.ndarray        # (n,) seconds after the window opens, ascending
    queries: np.ndarray    # (n, max_len) int32 query rows
    deadline_ms: float


def make_schedule(traffic: dict, seconds: float, seed: int, freq: np.ndarray,
                  exclude: np.ndarray, query_law: dict) -> Schedule:
    law = traffic["law"]
    if law not in LAWS:
        raise ValueError(f"traffic law {law!r} is not one of {LAWS}")
    n = int(round(float(traffic["rate_qps"]) * seconds))
    if n < 1:
        raise ValueError(f"{traffic['rate_qps']} q/s for {seconds} s "
                         "offers no request")
    work_seed = int(traffic.get("work_seed", 0))
    gaps = np.random.default_rng([work_seed, 0x51ED27]).exponential(
        1.0, n - 1)
    if n > 1:
        gaps *= seconds * (n - 1) / n / gaps.sum()
    queries = data.query_pool(freq, n, work_seed, exclude=exclude,
                              **query_law)
    order = np.random.default_rng([int(seed), 0x0DE5])
    due = np.concatenate([[0.0], np.cumsum(order.permutation(gaps))])
    return Schedule(due=due, queries=queries,
                    deadline_ms=float(traffic["deadline_ms"]))
