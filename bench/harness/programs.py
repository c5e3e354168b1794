"""What a chip trace says of the program by the names the program gives:
the runs of its named XLA modules, and its host annotations.

The program lowers each scheduler program as module ``jit_sched_<name>``
and each engine stage as ``jit_engine_<stage>``; a device plane's ``XLA
Modules`` line holds one event per module run, named ``<module>(<id>)``.
While tracing is on, every context span of the program is also a host
annotation of the same name, in the ``/host:CPU`` plane on the trace's
clock (``TraceSummary.host``).

Readers get the module runs from ``module_runs``: the summary's
``modules`` where it has them, else read from the ``.xplane.pb`` the run
left under ``bench/.cache/trace/<cell>`` (``run.py``'s ``_Profiler``).
A program without such names leaves nothing to read, and the readers
then return None.
"""

from __future__ import annotations

import collections
import re
from pathlib import Path

__all__ = ["Module", "read_modules", "module_runs", "ms_per_run",
           "idle_share_in"]

_RUN_ID = re.compile(r"\(\d+\)$")


#: one module run: its name without the run id, start and duration (ns)
Module = collections.namedtuple("Module", "name start_ns dur_ns")


def read_modules(path, window_ns) -> list:
    """The module runs of the first chip in ``path`` that start inside
    ``window_ns`` (start, end on the trace clock)."""
    import jax

    pd = jax.profiler.ProfileData.from_file(str(path))
    chips = {}
    for plane in pd.planes:
        m = re.fullmatch(r"/device:TPU:(\d+)", plane.name)
        if not m:
            continue
        for line in plane.lines:
            if line.name == "XLA Modules":
                chips[int(m.group(1))] = [
                    Module(_RUN_ID.sub("", e.name), e.start_ns,
                           e.duration_ns) for e in line.events]
    if not chips:
        return []
    w0, w1 = window_ns
    return [r for r in chips[min(chips)] if w0 <= r.start_ns < w1]


def module_runs(run, bench_dir: Path) -> list | None:
    """The module runs in ``run``'s traced stretch, or None when it was
    not traced on a chip.  ``bench_dir`` is the ``bench`` directory of
    the checkout the run came from."""
    summary = run.trace
    if summary is None:
        return None
    mods = getattr(summary, "modules", None)
    if mods is not None:
        return list(mods)
    found = sorted((Path(bench_dir) / ".cache" / "trace" / run.cell)
                   .rglob("*.xplane.pb"))
    return read_modules(found[-1], summary.window_ns) if found else None


def ms_per_run(mods, names, per: str) -> float | None:
    """Device ms of the modules ``names`` over the number of runs of
    ``per``; None when ``per`` never ran."""
    if not mods:
        return None
    n = sum(1 for r in mods if r.name == per)
    if n == 0:
        return None
    return sum(r.dur_ns for r in mods if r.name in names) / n / 1e6


def idle_share_in(summary, names) -> float | None:
    """Share (%) of the chip's idle time in the traced stretch that falls
    inside the host annotations ``names``; None when the trace holds none
    of them or no idle time."""
    if summary is None:
        return None
    marks = sorted((s, e) for n, s, e in summary.host if n in names)
    idle = sum(g1 - g0 for g0, g1 in summary.gaps)
    if not marks or idle <= 0:
        return None
    merged = []
    for s, e in marks:
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    inside, j = 0.0, 0
    for g0, g1 in sorted(summary.gaps):
        while j < len(merged) and merged[j][1] <= g0:
            j += 1
        k = j
        while k < len(merged) and merged[k][0] < g1:
            inside += min(g1, merged[k][1]) - max(g0, merged[k][0])
            k += 1
    return 100.0 * inside / idle
