"""Chunk ticks a request's slot ran, on average: the program's
``sched.chunk_ticks`` over ``sched.finalized``.  Under ρ a slot retires
once its budget is spent; under k every slot runs its whole stream.  The
counters cover every request since the service started, warm-up
requests too.  None where the program keeps no such counters or where
nothing was finalized."""


def read(run):
    c = run.counters
    n = c.get("sched.finalized", 0)
    if "sched.chunk_ticks" not in c or not n:
        return None
    return c["sched.chunk_ticks"] / n
