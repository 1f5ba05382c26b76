"""Host time per dispatched batch, in ms: the median over the program's
``serve.batch`` spans in the traced window of each span's length less the
part of it its ``plan.fetch`` spans cover (waiting for the device and
copying the logits back). What is left is assembly, padding, launch with
the input copy, and completion. No such spans in the trace: no reading."""
import statistics

BATCH, FETCH = "serve.batch", "plan.fetch"


def read(run):
    if run.trace is None:
        return None
    lo, hi = run.trace["window"]
    spans = [e for e in run.trace["host"] if lo <= e[2] and e[2] + e[3] <= hi]
    fetches = [e for e in spans if e[1] == FETCH]
    host = []
    for thread, name, s, d in spans:
        if name != BATCH:
            continue
        waited = sum(fd for t, _, fs, fd in fetches
                     if t == thread and s <= fs and fs + fd <= s + d)
        host.append((d - waited) / 1e6)
    return statistics.median(host) if host else None
