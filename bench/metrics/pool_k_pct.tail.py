"""Share of the static pool that the cascade asked for: 100 × the mean
predicted k over the requests the scheduler finalized (the program's
``sched.k_width`` over ``sched.finalized``) ÷ the largest cutoff, which
is the width that pool select and stage 2 always run at.  The counters
cover every request since the service started, warm-up requests too.
None where the program keeps no such counters, on a knob other than k,
or where nothing was finalized."""


def read(run):
    c = run.counters
    if run.config["serving"]["knob"] != "k" or "sched.k_width" not in c:
        return None
    n = c.get("sched.finalized", 0)
    if not n:
        return None
    top = max(run.config["serving"]["cutoffs"])
    return 100.0 * c["sched.k_width"] / n / top
