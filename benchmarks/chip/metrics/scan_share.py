"""Share of the batch-answered queries whose batch ran the exhaustive
scan plan (the server's per-plan query counts)."""


def read(run):
    plans = run.plan_queries()
    n = sum(plans.values())
    return 100.0 * plans.get("scan", 0) / n if n else None
