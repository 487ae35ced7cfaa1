"""95th percentile of the latencies that ``latency_p50_ms`` reads."""
import numpy as np


def read(run):
    if not run.latencies_s:
        return None
    return float(np.percentile(np.asarray(run.latencies_s), 95) * 1e3)
