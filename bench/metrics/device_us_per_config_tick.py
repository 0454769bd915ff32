"""Engine tick: device busy time of the traced call per configuration-tick."""


def read(run):
    if run.trace is None or run.trace.busy_s <= 0:
        return None
    return run.trace.busy_s * 1e6 / run.traced_call.config_ticks
