"""Compiles JAX made inside the window (backend compiles and programs
loaded from the persistent cache); every shape is warmed in set-up, so
this should read 0."""


def read(run):
    return run.window_compiles
