"""95th percentile, over every request due in the window, of the time from
its due time to its result (nearest rank); a request with no result
counts as later than every result."""

from harness.record import nearest_rank


def read(run):
    return nearest_rank(run.latencies_ms(), 0.95)
