"""Chip peaks and the least work of a kernel call, for roofline shares.

A kernel's share of its roofline is the least time the chip could take
for the work the kernel was given — the larger of its operations over
the peak rate and its bytes over the peak bandwidth — divided by the
time the kernel's events took on the device.  The work is counted from
the call's operand and result shapes and the postings it is given, never
from the kernel's grid, block sizes or formulation, so the count holds
when the formulation changes.
"""

from __future__ import annotations

__all__ = ["PEAKS", "peaks", "impact_scan_work", "roofline_share"]

#: per ``device_kind``: peak FLOP/s (bf16), HBM bytes/s, HBM bytes.
#: Source: Google Cloud documentation, "TPU v5e" (197 TFLOP/s bf16,
#: 819 GB/s, 16 GB HBM per chip).
PEAKS = {
    "TPU v5 lite": {"flops_per_s": 197e12, "bytes_per_s": 819e9,
                    "hbm_bytes": 16e9,
                    "source": "Google Cloud documentation, TPU v5e"},
}


def peaks(device_kind: str) -> dict:
    """The peaks of ``device_kind``; a chip that is not in the table is an
    error, never a default."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no peaks for device kind {device_kind!r}; add it "
                       f"to bench/harness/roofline.py with its source"
                       ) from None


def impact_scan_work(result, operands, n_docs: int) -> tuple[float, float]:
    """(operations, bytes) of one ``impact_scan`` call.

    ``result`` and ``operands`` are (dtype, dims, bytes) lists as
    ``trace.parse_shapes`` gives them.  The call is given a (Q, P)
    doc-id stream and a (Q, P) impact stream (with per-query budgets and
    segment bounds) and writes a dense (Q, n_docs) float32 accumulator:
    one add per posting given, every operand read once, the accumulator
    written once.  The result's columns past ``n_docs`` are padding and
    are not counted."""
    streams = [o for o in operands if len(o[1]) == 2]
    if len(streams) != 2 or streams[0][1] != streams[1][1]:
        raise ValueError(f"impact_scan call without its two (Q, P) "
                         f"streams: {operands}")
    q, p = streams[0][1]
    ops = float(q * p)
    read = float(sum(o[2] for o in operands))
    (dtype, (rq, rn), _), = result
    written = float(rq * min(rn, n_docs) * 4)
    return ops, read + written


def roofline_share(calls, pk: dict) -> tuple[float, str] | None:
    """(percent of roofline, binding resource) over ``calls``, a list of
    (operations, bytes, seconds on the device); None without calls."""
    if not calls:
        return None
    t_ops = sum(c[0] for c in calls) / pk["flops_per_s"]
    t_bytes = sum(c[1] for c in calls) / pk["bytes_per_s"]
    spent = sum(c[2] for c in calls)
    if spent <= 0:
        return None
    return (100.0 * max(t_ops, t_bytes) / spent,
            "memory" if t_bytes >= t_ops else "compute")
