"""Median cascade predict time: the program's per-request ``predict_ms``."""

import statistics


def read(run):
    v = run.result_field_ms("predict_ms")
    return statistics.median(v) if v else None
