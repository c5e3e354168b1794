"""Device time per finalize step: ms per ``jit_sched_finalize`` module run
in the traced stretch (pool select, stage 2 and rerank of one retiring
group)."""

from pathlib import Path

from harness.programs import module_runs, ms_per_run


def read(run):
    mods = module_runs(run, Path(__file__).resolve().parents[1])
    return ms_per_run(mods, ("jit_sched_finalize",),
                      per="jit_sched_finalize")
