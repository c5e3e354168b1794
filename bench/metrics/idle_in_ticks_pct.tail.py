"""Share of the chip's idle time in the traced stretch that falls inside
the scheduler's step windows: the host annotations ``tick.finalize``,
``tick.refill`` and ``tick.chunk`` on the trace's own clock."""

from harness.programs import idle_share_in


def read(run):
    return idle_share_in(run.trace,
                         ("tick.finalize", "tick.refill", "tick.chunk"))
