"""Device milliseconds per MoE layer under RaFI's own scopes in the traced
window: the self time (mean over chips) of every ``rafi.*`` scope that is
not a ``rafi.moe_*`` one (``rafi.forward`` and the stage scopes beneath it,
the drive, the app rounds that land the arrivals, the enqueues), both
trips, over the layers of the traced jobs.  A run whose trace names no
``rafi.moe_*`` scope ran no MoE layer, and reads nothing."""


def read(run):
    if run.trace is None or not any(s.startswith("rafi.moe_") for s in run.trace.scopes):
        return None
    seconds = sum(t for s, t in run.trace.scopes.items()
                  if s.startswith("rafi.") and not s.startswith("rafi.moe_"))
    layers = sum(j["layers"] for j in run.traced)
    return seconds / layers * 1e3 if seconds and layers else None
