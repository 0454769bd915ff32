"""``drain_gap_ms`` in the short-call cell, where it moves
``config_ticks_per_s.short_calls``."""
from bench import scopes


def read(run):
    return scopes.drain_gap_ms(run)
