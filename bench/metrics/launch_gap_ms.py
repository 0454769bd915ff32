"""Front door, execute: device idle time from the start of the traced
call's ``repro.execute`` span to the first device op after it."""
from bench import scopes


def read(run):
    return scopes.launch_gap_ms(run)
