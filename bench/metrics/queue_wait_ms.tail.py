"""Admission wait of the tail: 95th percentile (nearest rank) of the
program's per-request ``queue_ms``, submit to admission.  The slot
scheduler stamps admission with the start of the tick that admits, so a
request submitted during that tick reads up to one finalize early."""

from harness.record import nearest_rank


def read(run):
    v = run.result_field_ms("queue_ms")
    return nearest_rank(v, 0.95) if v else None
