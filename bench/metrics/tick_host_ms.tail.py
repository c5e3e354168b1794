"""Host time per working scheduler tick: the mean of the program's
``tick`` spans in the window."""

import statistics


def read(run):
    v = run.span_durations_ms("tick")
    return statistics.fmean(v) if v else None
