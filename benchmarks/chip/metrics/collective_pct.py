"""Device time of XLA collective operations (all-to-all, all-reduce,
all-gather, collective-permute) as a share of device busy time in the
traced window, mean over chips, in percent."""


def read(run):
    if run.trace is None or run.trace.collective_s <= 0:
        return None
    return 100.0 * run.trace.collective_s / run.trace.busy_s
