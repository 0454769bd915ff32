"""Store read: share of the traced call's device busy time under the
program's ``gather`` scope (``engine.read_rows*``), whatever implements
the read: the Pallas multi-read with its packing copies, or an XLA gather."""
from bench import scopes


def read(run):
    return scopes.gather_share(run)
