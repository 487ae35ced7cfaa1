"""Median t_exec to t_done of the window's scan batches, from the
server's batch spans (traced run)."""
import numpy as np


def read(run):
    xs = [b - a for label, a, b, _ in run.batch_spans if label == "scan"]
    return float(np.median(xs) * 1e3) if xs else None
