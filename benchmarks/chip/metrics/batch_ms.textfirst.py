"""Median t_exec to t_done of the window's TEXT-FIRST batches, from the
server's batch spans (traced run)."""
import numpy as np


def read(run):
    xs = [b - a for label, a, b, _ in run.batch_spans if label.startswith("text_first")]
    return float(np.median(xs) * 1e3) if xs else None
