"""deliveries_per_s: items that arrived in some rank's incoming queue, over
the whole window, per second of it (host clock)."""


def read(run):
    return sum(j["deliveries"] for j in run.jobs) / run.window_s
