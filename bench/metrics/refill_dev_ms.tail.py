"""Device time per refill step: the ``jit_sched_sgather`` and
``jit_sched_refill`` module runs of the traced stretch, in ms, over the
number of refill runs."""

from pathlib import Path

from harness.programs import module_runs, ms_per_run


def read(run):
    mods = module_runs(run, Path(__file__).resolve().parents[1])
    return ms_per_run(mods, ("jit_sched_sgather", "jit_sched_refill"),
                      per="jit_sched_refill")
