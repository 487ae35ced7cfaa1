"""Median latency of every query answered in the window, admission into
the server to its answer: cache hits, coalesced and executed alike."""
import numpy as np


def read(run):
    if not run.latencies_s:
        return None
    return float(np.percentile(np.asarray(run.latencies_s), 50) * 1e3)
