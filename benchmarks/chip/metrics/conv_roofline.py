"""The compressed-conv kernels' share of their roofline, in %: Σ least time
÷ Σ device time over every compressed-conv kernel call in the traced
window. Least time per call is the larger of its ops over the int8 peak and
its bytes over HBM bandwidth (``costs``), at the call's own batch.

A call is a ``tpu_custom_call`` whose output (N, Ho, Wo, F) and input
channels C match a compressed conv of the configuration; the stem (C = 3)
and the head do not match. Nothing matching in the trace: no reading."""
import costs
import devtrace


def read(run):
    if run.trace is None:
        return None
    convs = [l for l in costs.layers(run.config) if l["kind"] == "conv"]
    least = spent = 0.0
    for ev in run.trace["devices"].values():
        for name, _, dur in devtrace.in_window(run.trace, ev):
            call = devtrace.custom_call_shapes(name)
            if call is None:
                continue
            out, first = call
            for layer in convs:
                if (len(out) == 4 and tuple(out[1:]) == (layer["ho"], layer["wo"], layer["cout"])
                        and first and first[-1] == layer["cin"]):
                    least += costs.least_time_s(layer, out[0], run.peaks)
                    spent += dur / 1e9
                    break
    return 100.0 * least / spent if spent else None
