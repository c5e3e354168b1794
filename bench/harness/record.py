"""What one run leaves for the metric readers (``bench/metrics/*.py``)."""

from __future__ import annotations

import dataclasses
import math

__all__ = ["Run", "nearest_rank"]


def nearest_rank(values, q: float) -> float:
    """The ``q`` quantile (0..1) by nearest rank; values may hold inf."""
    v = sorted(values)
    if not v:
        return float("nan")
    return v[max(0, math.ceil(q * len(v)) - 1)]


@dataclasses.dataclass
class Run:
    cell: str
    config: dict
    traffic: dict
    seconds: float
    t_open: float               # perf_counter seconds
    t_stop: float               # when the run stopped waiting for results
    setup_s: float
    outcomes: list              # serve.Outcome, one per request due
    window_compiles: int        # compiles JAX made inside the window
    in_envelope_pct: float | None
    spans: list = dataclasses.field(default_factory=list)   # obs spans
    counters: dict = dataclasses.field(default_factory=dict)
    trace: object = None        # trace.TraceSummary of the traced stretch
    peaks: dict | None = None   # roofline.PEAKS entry of the chip

    @property
    def t_close(self) -> float:
        return self.t_open + self.seconds

    def latencies_ms(self) -> list:
        """Due time to result, per request due.  A request with no result
        counts from its due time to when the run stopped waiting, which is
        later than every result that came."""
        return [((o.done if o.result is not None else self.t_stop)
                 - o.due) * 1e3 for o in self.outcomes]

    def results(self) -> list:
        return [o.result for o in self.outcomes if o.result is not None]

    def span_durations_ms(self, prefix: str) -> list:
        """Durations of the program's spans named ``prefix`` or
        ``prefix:<anything>``."""
        return [(s.t1 - s.t0) * 1e3 for s in self.spans
                if (s.name == prefix or s.name.startswith(prefix + ":"))
                and s.t1 >= 0]

    def result_field_ms(self, key: str) -> list:
        return [r[key] for r in self.results() if r.get(key) is not None]

    def device_idle_pct(self) -> float | None:
        if self.trace is None or self.trace.window_s <= 0:
            return None
        return 100.0 * (1.0 - self.trace.busy_s / self.trace.window_s)

    def roofline_pct(self, kernel: str, work) -> float | None:
        """Share of its roofline that ``kernel`` reached over the traced
        stretch; ``work(result, operands, n_docs)`` counts a call."""
        from harness import roofline, trace
        if self.trace is None or self.peaks is None:
            return None
        n_docs = int(self.config["collection"]["n_docs"])
        calls = []
        for op in self.trace.kernel_ops(kernel):
            res, args = trace.parse_shapes(op.name)
            calls.append((*work(res, args, n_docs), op.dur_ns / 1e9))
        share = roofline.roofline_share(calls, self.peaks)
        return None if share is None else share[0]
