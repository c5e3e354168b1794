"""Reduction of a JAX profiler trace (``.xplane.pb``) to device numbers.

On a TPU the trace has one plane per chip, ``/device:TPU:<i>``, whose
``XLA Ops`` line holds one event per operation run, named by its HLO
text: ``%impact_scan.1 = f32[64,2211840]{...} custom-call(s32[64]{...}
%x, ...)``.  Host threads are lines of the ``/host:CPU`` plane, on the
same clock; the run marks the stretch it traced with a host annotation
(``WINDOW``), and everything is clipped to that stretch.

* busy: the union of the op intervals of a chip, averaged over chips;
* idle gaps: the stretches between busy intervals;
* per-op time: summed durations, grouped by op name (numeric suffix
  dropped) and result shape;
* kernel events: the ops whose name starts with a kernel's name, with
  the shapes of their result and operands parsed from the HLO text.
"""

from __future__ import annotations

import collections
import dataclasses
import re

__all__ = ["WINDOW", "Op", "TraceSummary", "reduce_trace", "parse_shapes"]

#: the host annotation around the traced stretch of a window
WINDOW = "bench.window"

_SHAPE = re.compile(r"\b(pred|s8|u8|s16|u16|s32|u32|s64|u64|bf16|f16|f32|f64)"
                    r"\[([0-9,]*)\]")
_BYTES = {"pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "bf16": 2,
          "f16": 2, "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8,
          "f64": 8}


@dataclasses.dataclass
class Op:
    name: str          # HLO text of the op
    start_ns: float
    dur_ns: float
    chip: int


@dataclasses.dataclass
class TraceSummary:
    window_ns: tuple            # (start, end) on the trace clock
    n_chips: int
    busy_ns: float              # union of op intervals, mean over chips
    ops: list                   # every Op inside the window
    gaps: list                  # (start_ns, end_ns) idle stretches, chip 0
    host: list                  # (name, start_ns, end_ns) host events

    @property
    def window_s(self) -> float:
        return (self.window_ns[1] - self.window_ns[0]) / 1e9

    @property
    def busy_s(self) -> float:
        return self.busy_ns / 1e9

    def op_seconds(self) -> dict:
        """Seconds per op kind: name without its numeric suffix, with its
        result shape."""
        out = collections.Counter()
        for op in self.ops:
            out[op_kind(op.name)] += op.dur_ns / 1e9
        return dict(out)

    def kernel_ops(self, kernel: str) -> list:
        pat = re.compile(rf"^%{re.escape(kernel)}(\.\d+)? = ")
        return [op for op in self.ops if pat.match(op.name)]


def op_kind(hlo: str) -> str:
    head, _, rest = hlo.partition(" = ")
    base = re.sub(r"\.\d+$", "", head.lstrip("%"))
    m = _SHAPE.search(rest)
    return f"{base} {m.group(0)}" if m else base


def parse_shapes(hlo: str):
    """(result shapes, operand shapes) of an op's HLO text, each a list of
    (dtype, dims, bytes)."""
    _, _, rest = hlo.partition(" = ")
    for m in re.finditer(r"(?:^|\s)([a-z][\w\-]*)\(", rest):
        head = rest[:m.start()]
        if head.count("(") == head.count(")") and \
                head.count("{") == head.count("}"):
            open_at = m.end() - 1
            break
    else:
        return _shapes(rest), []
    depth = 0
    for i in range(open_at, len(rest)):
        depth += rest[i] == "("
        depth -= rest[i] == ")"
        if depth == 0:
            break
    return _shapes(rest[:open_at]), _shapes(rest[open_at:i + 1])


def _shapes(txt: str) -> list:
    out = []
    for m in _SHAPE.finditer(txt):
        dims = tuple(int(x) for x in m.group(2).split(",") if x)
        n = 1
        for d in dims:
            n *= d
        out.append((m.group(1), dims, n * _BYTES[m.group(1)]))
    return out


def _union(intervals):
    total, merged = 0.0, []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    for s, e in merged:
        total += e - s
    return total, merged


def reduce_trace(path: str, n_chips: int | None = None):
    """Read one ``.xplane.pb`` and reduce it to the window it marks; None
    when the trace holds no TPU (a run off the chip)."""
    import jax

    pd = jax.profiler.ProfileData.from_file(path)
    host, chips = [], {}
    for plane in pd.planes:
        m = re.fullmatch(r"/device:TPU:(\d+)", plane.name)
        if m:
            for line in plane.lines:
                if line.name == "XLA Ops":
                    chips[int(m.group(1))] = [
                        Op(e.name, e.start_ns, e.duration_ns,
                           int(m.group(1))) for e in line.events]
        elif plane.name == "/host:CPU":
            for line in plane.lines:
                for e in line.events:
                    host.append((e.name, e.start_ns,
                                 e.start_ns + e.duration_ns))
    if not chips:
        return None
    marks = [(s, e) for n, s, e in host if n == WINDOW]
    if marks:
        w0, w1 = marks[0]
    else:
        every = [op for ops in chips.values() for op in ops]
        w0 = min(op.start_ns for op in every)
        w1 = max(op.start_ns + op.dur_ns for op in every)
    ids = sorted(chips)[:n_chips] if n_chips else sorted(chips)
    ops, busy, gaps = [], 0.0, []
    for c in ids:
        inside = [op for op in chips[c]
                  if op.start_ns < w1 and op.start_ns + op.dur_ns > w0]
        ops += inside
        b, merged = _union((max(op.start_ns, w0),
                            min(op.start_ns + op.dur_ns, w1))
                           for op in inside)
        busy += b
        if c == ids[0]:
            edges = [w0] + [x for s, e in merged for x in (s, e)] + [w1]
            gaps = [(edges[i], edges[i + 1])
                    for i in range(0, len(edges), 2)
                    if edges[i + 1] > edges[i]]
    host = [h for h in host if h[1] < w1 and h[2] > w0]
    return TraceSummary(window_ns=(w0, w1), n_chips=len(ids),
                        busy_ns=busy / len(ids), ops=ops, gaps=gaps,
                        host=host)
