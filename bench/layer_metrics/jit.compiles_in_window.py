"""Compile: programs JAX lowered inside the measured window (a new shape
or a new function, compiled or loaded from the persistent cache). It
should be 0."""


def read(run):
    return float(run.compiles_in_window)
