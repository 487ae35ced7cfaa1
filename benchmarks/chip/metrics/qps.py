"""Queries answered in the window over its wall seconds (first admission
to the last answer, whole chunks included)."""


def read(run):
    return run.queries / run.window_s if run.window_s > 0 else None
