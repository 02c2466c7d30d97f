"""Device milliseconds per forwarding round in the ``rafi.marshal`` scope
(``core/stages`` ``Marshal``: the send buffer's slot index and row gather)
in the traced window: the scope's self time (mean over chips) over the
rounds of the traced jobs."""
from tracefile import scope_ms_per_round


def read(run):
    return scope_ms_per_round(run, "rafi.marshal")
