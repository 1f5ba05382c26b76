"""Mean time a request waited in the server's queue before its batch was
dispatched, in ms (``ServerStats.queue_wait_s`` over
``dispatched_requests``, host clock). A server without these counters: no
reading."""


def read(run):
    n = getattr(run.stats, "dispatched_requests", 0)
    return 1e3 * run.stats.queue_wait_s / n if n else None
