"""Device milliseconds per forwarding round in the ``rafi.enqueue`` scope
(``core/queue`` ``enqueue`` and ``make_queue``: the app's emits and the
seeding) in the traced window: the scope's self time (mean over chips)
over the rounds of the traced jobs."""
from tracefile import scope_ms_per_round


def read(run):
    return scope_ms_per_round(run, "rafi.enqueue")
