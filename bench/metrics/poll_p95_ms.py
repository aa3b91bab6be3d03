"""95th percentile of the host-clock time of every ``poll()`` in the
window (each returns host floats, so each time is complete)."""
import numpy as np


def read(run):
    polls = run.latencies.get("poll")
    if not polls:
        return None
    return float(np.percentile(np.asarray(polls) * 1e3, 95))
