"""Host time the tick thread waits on device results, per working tick:
the program's ``sched.sync`` spans (the stream-length read-back of a
refill, the ranked read-back of a finalize) summed, over its ``tick``
spans."""


def read(run):
    sync = run.span_durations_ms("sched.sync")
    ticks = run.span_durations_ms("tick")
    return sum(sync) / len(ticks) if sync and ticks else None
