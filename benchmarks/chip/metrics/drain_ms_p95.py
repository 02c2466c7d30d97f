"""drain_ms_p95: 95th percentile over every burst of the window of the time
from dispatching its seeds to the host holding its termination (host
clock, milliseconds)."""
import numpy as np


def read(run):
    return float(np.percentile([(j["t1"] - j["t0"]) * 1e3 for j in run.jobs], 95))
