"""Kernel plane: share of the traced call's device busy time spent in Pallas kernels."""


def read(run):
    if run.trace is None or run.trace.busy_s <= 0 or run.trace.pallas_s <= 0:
        return None
    return 100.0 * run.trace.pallas_s / run.trace.busy_s
