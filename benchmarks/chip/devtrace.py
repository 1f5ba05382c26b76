"""Profiler trace of a window, and its reduction to device metrics.

:class:`Tracer` records the window with JAX's profiler (Python call
tracing off, so only the host spans the benchmark marks and the runtime's
own are recorded) and reduces the ``.xplane.pb`` to a small dict:

- ``window``: [start_ns, end_ns] of the benchmark's ``bench.window`` span;
- ``devices``: per chip, [name, start_ns, duration_ns] of every operation
  on its ``XLA Ops`` line;
- ``host``: [thread, name, start_ns, duration_ns] of host spans.

The functions below read only that dict, so they are checked on a small
trace recorded on the chip (``tests/data``). Every time is on the trace's
one clock.
"""
from __future__ import annotations

import collections
import glob
import heapq
import os
import re
import shutil
import tempfile

DEVICE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
HOST_PLANE = "/host:CPU"
WINDOW = "bench.window"
TOP = 10


class Tracer:
    def __init__(self):
        self.dir = None

    def start(self) -> None:
        import jax

        self.dir = tempfile.mkdtemp(prefix="bench-trace-")
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(self.dir, profiler_options=opts)

    def stop(self) -> None:
        import jax

        jax.profiler.stop_trace()

    def span(self, name: str):
        import jax

        return jax.profiler.TraceAnnotation(name)

    def reduce(self) -> dict:
        """Read the recorded trace into the reduced dict, then delete it."""
        try:
            paths = glob.glob(os.path.join(self.dir, "plugins", "profile", "*",
                                           "*.xplane.pb"))
            if len(paths) != 1:
                raise RuntimeError(f"expected one trace file, found {paths}")
            return read_xplane(paths[0])
        finally:
            shutil.rmtree(self.dir, ignore_errors=True)


def read_xplane(path: str) -> dict:
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    devices, host, window = {}, [], None
    for plane in data.planes:
        if plane.name.startswith(DEVICE_PREFIX):
            for line in plane.lines:
                if line.name == OPS_LINE:
                    devices[plane.name] = [[e.name, int(e.start_ns), int(e.duration_ns)]
                                           for e in line.events]
        elif plane.name == HOST_PLANE:
            for line in plane.lines:
                for e in line.events:
                    if e.duration_ns <= 0:
                        continue
                    if e.name == WINDOW:
                        window = [int(e.start_ns), int(e.start_ns + e.duration_ns)]
                    host.append([line.name, e.name, int(e.start_ns), int(e.duration_ns)])
    if window is None:
        raise RuntimeError(f"no {WINDOW} span in the trace")
    if not devices:
        raise RuntimeError("no device operations in the trace")
    return {"window": window, "devices": devices, "host": host}


def merged(events, lo: int, hi: int) -> list:
    """Union of [start, start + dur) intervals, clipped to [lo, hi)."""
    spans = sorted((max(s, lo), min(s + d, hi)) for _, s, d in events)
    out = []
    for a, b in spans:
        if b <= a:
            continue
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return out


_SHAPE = re.compile(r"[a-z]+\d*\[([\d,]*)\]")


def custom_call_shapes(name: str):
    """(output dims, first operand dims) of a trace event that is a Pallas
    kernel (``custom_call_target="tpu_custom_call"``), else None. The event
    name is the HLO instruction: ``%x = s8[N,H,W,F]{...} custom-call(s8[...]...``."""
    if 'custom_call_target="tpu_custom_call"' not in name:
        return None
    lhs, _, rhs = name.partition(" custom-call(")
    out, first = _SHAPE.search(lhs), _SHAPE.search(rhs)
    dims = lambda m: tuple(int(d) for d in m.group(1).split(",") if d) if m else ()  # noqa: E731
    return dims(out), dims(first)


def window_s(tr: dict) -> float:
    lo, hi = tr["window"]
    return (hi - lo) / 1e9


def busy_s(tr: dict) -> float:
    """Seconds in the window in which an operation ran, averaged over chips."""
    lo, hi = tr["window"]
    per = [sum(b - a for a, b in merged(ev, lo, hi)) for ev in tr["devices"].values()]
    return sum(per) / len(per) / 1e9


def idle_share(tr: dict) -> float:
    return 1.0 - busy_s(tr) / window_s(tr)


def in_window(tr: dict, events) -> list:
    lo, hi = tr["window"]
    return [e for e in events if lo <= e[1] and e[1] + e[2] <= hi]


def op_name(event_name: str) -> str:
    """An operation as the trace names it, cut before its layout: the HLO
    instruction's name and result type (``%quant_conv.7 = s8[128,64,64,64]``)."""
    return event_name.split("{", 1)[0].strip()


def device_ops(tr: dict) -> list:
    """The operations that took most device time in the window: [[name,
    seconds per chip], ...], at most ``TOP``."""
    total = collections.Counter()
    for ev in tr["devices"].values():
        for name, _, d in in_window(tr, ev):
            total[op_name(name)] += d
    n = len(tr["devices"])
    return [[name, t / n / 1e9] for name, t in total.most_common(TOP)]


def gaps(tr: dict, device: str | None = None) -> list:
    """[start, end] of the idle stretches of one chip in the window."""
    lo, hi = tr["window"]
    ev = tr["devices"][device or sorted(tr["devices"])[0]]
    out, t = [], lo
    for a, b in merged(ev, lo, hi):
        if a > t:
            out.append([t, a])
        t = b
    if t < hi:
        out.append([t, hi])
    return out


def activity_at(tr: dict, times: list) -> list:
    """What the host was doing at each of ``times``: the shortest host span
    around it, named ``thread:span`` (``bench.window`` itself where nothing
    shorter was marked). A sweep over the spans sorted by start."""
    spans = sorted(tr["host"], key=lambda e: e[2])
    order = sorted(range(len(times)), key=lambda k: times[k])
    out = ["untraced"] * len(times)
    shortest, j = [], 0
    for k in order:
        t = times[k]
        while j < len(spans) and spans[j][2] <= t:
            thread, name, s, d = spans[j]
            heapq.heappush(shortest, (d, s + d, name if name == WINDOW
                                      else f"{thread}:{name}"))
            j += 1
        while shortest and shortest[0][1] <= t:
            heapq.heappop(shortest)  # ended; a longer one may still hold t
        if shortest:
            out[k] = shortest[0][2]
    return out


def idle_gaps(tr: dict) -> list:
    """Idle device time of the first chip, by what the host was doing in
    the middle of each gap: [[activity, seconds], ...], at most ``TOP``."""
    idle = gaps(tr)
    names = activity_at(tr, [(a + b) // 2 for a, b in idle])
    total = collections.Counter()
    for (a, b), name in zip(idle, names):
        total[name] += b - a
    return [[name, t / 1e9] for name, t in total.most_common(TOP)]
