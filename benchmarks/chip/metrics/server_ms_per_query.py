"""Window wall time outside every batch's call (t_exec to t_done of the
server's batch events), per answered query: the server's, batcher's and
cache's host time."""


def read(run):
    if not run.queries:
        return None
    return (run.window_s - run.batch_service_s()) / run.queries * 1e3
