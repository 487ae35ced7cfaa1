"""The server's host work timed inside the program: the summed seconds of
the window's ``geo.plan``, ``geo.dispatch``, ``geo.stats`` and
``geo.deliver`` stages (``SpanRecorder.stage``, summed per chunk in
``ServeReport.stage_s``; traced run), per answered query.  Nothing where
the program times no stages."""

STAGES = ("geo.plan", "geo.dispatch", "geo.stats", "geo.deliver")


def read(run):
    sums = [getattr(r, "stage_s", None) or {} for r in run.reports]
    if not run.queries or not any(sums):
        return None
    return sum(s.get(k, 0.0) for s in sums for k in STAGES) / run.queries * 1e3
