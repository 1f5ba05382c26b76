"""The one load generator: a traffic mix's parameters in, a timed window out.

A mix (``traffic/<name>.json``) is data:

- ``loop``: ``"closed"`` — ``outstanding`` requests in flight, the next
  sent when the oldest completes (callers that each wait for a reply);
  or ``"open"`` — requests sent on a schedule whatever the server does
  (independent users), with ``arrivals`` ``"poisson"`` at ``rate_rps``.
- ``request_images``: images per request.
- ``pool_images``: request images are contiguous slices of a pool of this
  many images drawn from the seed, so a request costs no host time to
  make and every answer can be checked against the reference of its rows.
- the server's settings: ``buckets``, ``max_wait_ms``, and optionally
  ``dp`` (data-parallel chips).

Steadiness: every seed of an open loop gets the same multiset of gaps
(quantiles of the distribution), in an order drawn from the seed, so seeds
change which requests come when, not how much work a window holds.
"""
from __future__ import annotations

import collections
import concurrent.futures
import threading
import time

import numpy as np

# answers kept for the check, at most this many images' worth: a uniform
# sample drawn from the seed (reservoir) of every request due in the window
CHECK_IMAGES = 32768
# how long past the window's close a request may take before it is missing
DRAIN_S = 60.0


def _quantiles(n: int) -> np.ndarray:
    return (np.arange(n) + 0.5) / n


def arrivals(traffic: dict, seconds: float, rng) -> np.ndarray:
    """Send times (s from the window's start, all < ``seconds``) of an open
    loop: exactly ``rate_rps`` × ``seconds`` requests for every seed, the
    gaps between them one fixed set of exponential quantiles in an order
    drawn from the seed, scaled to span the window."""
    if traffic["arrivals"] != "poisson":
        raise ValueError(f"unknown arrivals {traffic['arrivals']!r}")
    n = max(1, round(seconds * float(traffic["rate_rps"])))
    gaps = rng.permutation(-np.log1p(-_quantiles(n)))
    return (np.cumsum(gaps) - gaps) * (seconds / gaps.sum())


class Window:
    """What one measured window recorded, per request: size, pool offset,
    scheduled and actual send time, completion time (None if it never
    came), the error type if it failed; the window's bounds; the sampled
    answers for the check."""

    def __init__(self):
        self.n, self.start, self.sched, self.sent = [], [], [], []
        self.done: dict = {}
        self.error: dict = {}
        self.answers: dict = {}
        self.t0 = self.t_end = None
        self._lock = threading.Lock()

    @property
    def seconds(self) -> float:
        return self.t_end - self.t0


class _Reservoir:
    """A uniform sample of ``k`` of the answers offered, drawn from ``rng``."""

    def __init__(self, k: int, rng):
        self.k, self.rng, self.seen, self.slots = k, rng, 0, []

    def offer(self, i: int, y) -> None:
        if self.seen < self.k:
            self.slots.append((i, y))
        else:
            j = int(self.rng.integers(0, self.seen + 1))
            if j < self.k:
                self.slots[j] = (i, y)
        self.seen += 1


def run(system, traffic: dict, pool: np.ndarray, seconds: float, seed: int,
        annotate=None) -> Window:
    """Drive ``system`` with the mix for ``seconds``; wait for every request
    sent (at most ``DRAIN_S`` past the close). ``annotate(name)`` gives a
    context manager that marks host spans in a profiler trace.

    Like a real client, the generator lets go of each request's future once
    it completes: a completion is recorded by the future's callback, so the
    window keeps no per-request object alive that the host's garbage
    collector would have to walk."""
    import contextlib

    note = annotate or (lambda name: contextlib.nullcontext())
    # one stream for the requests, one for the sample: request i is the
    # same for a seed however many requests a window completes
    rng, sample_rng = (np.random.default_rng(s) for s in
                       np.random.SeedSequence(seed).spawn(2))
    win = Window()
    n = int(traffic["request_images"])
    k = max(1, CHECK_IMAGES // n)
    if traffic["loop"] == "open":  # every request is known: sample up front
        schedule = arrivals(traffic, seconds, rng)
        sample = set(sample_rng.choice(len(schedule), min(k, len(schedule)),
                                       replace=False).tolist())
    keep = _Reservoir(k, sample_rng)
    inflight: dict = {}
    free = len(pool)

    def finished(f, i):
        t = time.perf_counter()
        try:
            y = f.result()
        except BaseException as e:  # noqa: BLE001 — failed: missing
            y, err = None, type(e).__name__
        with win._lock:
            if inflight.pop(i, None) is None:
                return  # given up on at the drain deadline
            if y is None:
                win.error[i] = err
            else:
                win.done[i] = t
                if traffic["loop"] == "open" and i in sample:
                    win.answers[i] = y

    def send(sched: float):
        i = len(win.n)
        start = int(rng.integers(0, free - n + 1))
        win.n.append(n)
        win.start.append(start)
        win.sched.append(sched)
        win.sent.append(time.perf_counter())
        try:
            with note("bench.send"):
                fut = system.submit(pool[start:start + n])
        except Exception as e:  # noqa: BLE001 — failed at submit: missing
            win.error[i] = type(e).__name__
            return i, None
        with win._lock:
            inflight[i] = fut
        fut.add_done_callback(lambda f, i=i: finished(f, i))
        return i, fut

    def drain() -> None:
        deadline = win.t_end + DRAIN_S
        with note("bench.wait"):
            while True:
                with win._lock:
                    left = list(inflight.values())
                if not left or time.perf_counter() >= deadline:
                    break
                concurrent.futures.wait(left, timeout=deadline - time.perf_counter())
        with win._lock:
            for i in list(inflight):
                win.error[i] = "NeverCame"
            inflight.clear()

    if traffic["loop"] == "closed":
        waiting = collections.deque()
        win.t0 = time.perf_counter()
        win.t_end = win.t0 + seconds
        for _ in range(int(traffic["outstanding"])):
            waiting.append(send(time.perf_counter() - win.t0))
        while waiting:
            i, fut = waiting.popleft()
            if fut is not None:
                with note("bench.wait"):
                    concurrent.futures.wait(
                        [fut], timeout=max(0.0, win.t_end + DRAIN_S - time.perf_counter()))
                if fut.done() and not fut.cancelled() and fut.exception() is None:
                    keep.offer(i, fut.result())
            if time.perf_counter() < win.t_end:
                waiting.append(send(time.perf_counter() - win.t0))
        drain()
        win.answers = dict(keep.slots)
    elif traffic["loop"] == "open":
        win.t0 = time.perf_counter()
        win.t_end = win.t0 + seconds
        for at in schedule:
            wait = win.t0 + at - time.perf_counter()
            if wait > 0:
                with note("bench.sleep"):
                    time.sleep(wait)
            send(float(at))
        drain()
    else:
        raise ValueError(f"unknown loop {traffic['loop']!r}")
    return win
