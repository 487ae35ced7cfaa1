"""Device time of ``text_probe``'s pruned walk in the traced window, per
TEXT-FIRST batch: the Pallas kernels (custom call ``tpu_custom_call``)
that run inside the program runs of TEXT-FIRST batches.  Nothing where
the window's program runs could not be paired with its batches."""

PLAN = "text_first"


def read(run):
    from benchmarks.chip.devtrace import kernel_seconds

    n = sum(1 for label, _, _, _ in run.batch_spans if label.startswith(PLAN))
    if run.device is None or not n:
        return None
    if not any(m[1].startswith(PLAN) for m in run.device.modules):
        return None
    seconds, count = kernel_seconds(run.device, "tpu_custom_call", program=PLAN)
    return seconds / n * 1e3 if count else None
