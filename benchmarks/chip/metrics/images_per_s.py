"""Images completed in the window, over the window (host clock).

Every request that completed before the window closed counts with all its
images; those still in flight at the close count for the check only."""


def read(run):
    w = run.window
    n = sum(w.n[i] for i, t in w.done.items() if t <= w.t_end and i not in w.error)
    return n / w.seconds
