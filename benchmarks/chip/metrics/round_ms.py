"""Device busy milliseconds per forwarding round in the traced window:
busy time (mean over chips) over the rounds of the traced jobs."""


def read(run):
    if run.trace is None:
        return None
    rounds = sum(j["rounds"] for j in run.traced)
    return run.trace.busy_s / rounds * 1e3 if rounds else None
