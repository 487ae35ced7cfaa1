"""Process start to the first admission of the window: corpus and stream,
index build and placement, compiles (or cache loads), warm-up."""


def read(run):
    return run.setup_s
