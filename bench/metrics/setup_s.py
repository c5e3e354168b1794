"""Set-up: process start to the first due arrival (load or build, server
start, compiles, warm-up)."""


def read(run):
    return run.setup_s
