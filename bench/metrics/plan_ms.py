"""Front door: median host time of ``repro.api.plan`` over the window's calls."""
import statistics


def read(run):
    return statistics.median(run.plan_ms) if run.plan_ms else None
