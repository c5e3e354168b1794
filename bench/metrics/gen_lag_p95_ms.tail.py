"""How late the load generator sent: 95th percentile of send time minus
due time (nearest rank)."""

from harness.record import nearest_rank


def read(run):
    lags = [(o.sent - o.due) * 1e3 for o in run.outcomes if o.sent == o.sent]
    return nearest_rank(lags, 0.95) if lags else None
