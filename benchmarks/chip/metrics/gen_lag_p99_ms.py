"""99th percentile of how late the load generator sent each request after
its scheduled time, in ms (host clock): a starved generator is not a fast
server."""
import numpy as np


def read(run):
    w = run.window
    if not w.n:
        return None
    lag = (np.asarray(w.sent) - w.t0 - np.asarray(w.sched)) * 1e3
    return float(np.percentile(lag, 99, method="higher"))
