"""Rounds of the scan's candidate loop (its ``scan_rounds`` counter, summed
over the batches' rows, padding rows included) per real row of the
window's scan batches (traced run)."""


def read(run):
    rows = sum(n for label, _, _, n in run.batch_spans if label == "scan")
    return run.plan_stat("scan", "scan_rounds") / rows if rows else None
