"""Share of the server's bucket dispatches launched while an earlier batch
was still unfetched, in % (``ServerStats.overlapped_batches`` over
``batches``, counted by the program): how often the device had the next
batch queued behind the one it ran. A server without the counter: no
reading."""


def read(run):
    overlapped = getattr(run.stats, "overlapped_batches", None)
    batches = getattr(run.stats, "batches", 0)
    return 100.0 * overlapped / batches if overlapped is not None and batches else None
