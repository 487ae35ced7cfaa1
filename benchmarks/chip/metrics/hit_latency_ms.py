"""Median latency of the window's cache hits, admission to answer (the
server's query spans, traced run): the result cache's own cost."""
import numpy as np


def read(run):
    xs = [lat for kind, lat in run.query_spans if kind == "hit"]
    return float(np.median(xs) * 1e3) if xs else None
