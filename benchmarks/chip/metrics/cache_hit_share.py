"""Share of the window's queries answered by the result cache or by
coalescing onto an in-flight twin; only where the deployment has either."""


def read(run):
    if not run.queries or not (run.config["cache"] or run.config["coalesce"]):
        return None
    return 100.0 * (run.total("cache_hits") + run.total("coalesced")) / run.queries
