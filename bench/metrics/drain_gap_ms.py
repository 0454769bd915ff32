"""Front door, execute: device idle time from the traced call's last
device op to the end of its ``repro.execute`` span."""
from bench import scopes


def read(run):
    return scopes.drain_gap_ms(run)
