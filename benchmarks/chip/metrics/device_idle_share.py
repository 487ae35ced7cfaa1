"""Share of the traced window in which no operation ran on the chip."""


def read(run):
    if run.device is None or run.device.window_s <= 0 or not run.device.ops:
        return None
    return 100.0 * (1.0 - run.device.busy_s / run.device.window_s)
