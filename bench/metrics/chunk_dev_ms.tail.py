"""Device time per chunk step: ms per ``jit_sched_chunk`` module run in
the traced stretch."""

from pathlib import Path

from harness.programs import module_runs, ms_per_run


def read(run):
    mods = module_runs(run, Path(__file__).resolve().parents[1])
    return ms_per_run(mods, ("jit_sched_chunk",), per="jit_sched_chunk")
