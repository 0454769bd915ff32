"""Compile: backend compiles inside the measured window (should be 0)."""


def read(run):
    return run.window_compiles
