"""95th percentile of the latencies of all the window's requests against
one preprocessed cluster (linear interpolation between order
statistics)."""
import numpy as np


def read(run):
    if run.mix.cluster != "once" or not run.requests:
        return None
    return float(np.percentile([q.latency_s for q in run.requests], 95))
