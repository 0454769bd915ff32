"""Compile: seconds of backend compiles during set-up, from JAX's monitoring events."""


def read(run):
    return run.setup_compile_s
