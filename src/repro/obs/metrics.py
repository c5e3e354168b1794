"""Named metrics registry: deterministic counters.

Naming scheme (docs/OBSERVABILITY.md): dotted lowercase
``<subsystem>.<what>[.<label>]`` — ``engine.dispatches``,
``sched.retired.rho_exhausted``, ``service.deadline_met``,
``online.swaps``.  The Prometheus exposition in ``export.py`` maps dots
to underscores and prefixes ``repro_``.

Counters are deterministic integers (dispatch counts, retirements by
reason, swaps, compiles, cancellations, stalls).  ``counters()``
snapshots them sorted by name — the ``obs_counters`` block committed in
``artifacts/BENCH_serving.json`` and the oracle-vs-kernel equality
oracle in ``tests/test_obs.py`` both read it.  Timings are spans
(``obs/trace.py``), not metrics.

Every counter shares the registry's single ``_lock``, which occupies one
position in the analyzer's ``LOCK_REGISTRY``: a *leaf*, innermost in
the global order (service → admission → scheduler → swap → cache →
obs).  Recording from inside any other serving lock is therefore legal;
nothing is ever called while holding it.  Hot-path recording is
lock+add: instrumented classes bind their metric objects once at
``bind_obs`` time instead of doing a registry lookup per event.

A disabled registry hands out the shared no-op ``NULL_METRIC`` so hot
paths carry no conditionals; ``enabled`` is fixed at construction.
"""

from __future__ import annotations

import threading


class _NullMetric:
    """No-op stand-in for a counter; shared singleton."""

    __slots__ = ()

    def inc(self, n: int = 1) -> None:
        pass

    def value(self):
        return 0


NULL_METRIC = _NullMetric()


class Counter:
    """Monotone deterministic integer; use only for machine-independent
    event counts (the CI diff-check depends on it)."""

    __slots__ = ("name", "_lock", "_value")

    def __init__(self, name: str, lock):
        self.name = name
        self._lock = lock
        self._value = 0

    def inc(self, n: int = 1) -> None:
        with self._lock:
            self._value += n

    def value(self) -> int:
        with self._lock:
            return self._value


class MetricsRegistry:
    """Get-or-create registry; one lock shared by every metric."""

    def __init__(self, enabled: bool = True):
        self.enabled = bool(enabled)
        self._lock = threading.Lock()
        self._metrics: dict = {}

    def counter(self, name: str) -> Counter:
        if not self.enabled:
            return NULL_METRIC
        with self._lock:
            m = self._metrics.get(name)
            if m is None:
                m = self._metrics[name] = Counter(name, self._lock)
            return m

    def counters(self) -> dict:
        """Every counter, sorted by name — the diff-checked surface."""
        with self._lock:
            return {n: m._value for n, m in sorted(self._metrics.items())}

    def snapshot(self) -> dict:
        """The counters, under the key an exporter reads."""
        return {"counters": self.counters()}


#: shared disabled registry — every lookup returns NULL_METRIC
NULL_REGISTRY = MetricsRegistry(enabled=False)
