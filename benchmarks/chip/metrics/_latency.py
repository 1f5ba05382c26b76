"""Latency of every request sent in the window, from the time it was due
(its scheduled send time, so a late generator or a stalled server shows)
to the completion of its future, in ms; a request that failed or never came
counts as infinitely late."""
import math

import numpy as np


def latencies_ms(run) -> np.ndarray:
    w = run.window
    out = np.full(len(w.n), math.inf)
    for i, t in w.done.items():
        if i not in w.error:
            out[i] = (t - (w.t0 + w.sched[i])) * 1e3
    return out


def percentile(run, q: float):
    lat = latencies_ms(run)
    if not len(lat):
        return None
    v = float(np.percentile(lat, q, method="higher"))
    return v if math.isfinite(v) else None
