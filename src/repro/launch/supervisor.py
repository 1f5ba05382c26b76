"""Self-healing serving lifecycle: the §15 supervision layer.

:class:`Supervisor` owns a :class:`~repro.launch.server.CNNServer` and
keeps it serving through the failures the §14 request layer cannot
absorb — the dispatcher process itself dying, the weights on disk going
bad, a compiled kernel path breaking:

- **Supervised restart.** A dispatcher crash hands its
  admitted-but-undispatched requests back through the server's
  ``on_crash`` seam; the supervisor restarts the dispatcher after a
  bounded exponential backoff with deterministic jitter and *requeues*
  them — their futures resolve after the restart as if nothing happened.
  Requests that were inside a dispatch at crash time fail typed
  (``ServerCrashed``): at-most-once, never silently re-executed. The
  restarted server keeps the same books (``start(fresh_stats=False)``),
  so ``completed+rejected+failed+expired == offered`` holds across every
  restart, with ``restarts``/``requeued`` counting the journey.
- **Crash-loop circuit breaker.** More than ``max_restarts`` crashes
  inside ``window_s`` opens the breaker: the server stays down,
  ``health()`` reports ``'failed'`` with the reason, and the requests
  from the final crash fail typed instead of looping forever.
- **Hot reload** (:meth:`reload`). Restore a checkpoint through the §15
  integrity verification (``CorruptCheckpointError`` on any damage —
  the old plan keeps serving), rebuild quantize→plan *off* the
  dispatcher thread (keeping the serving ``sample_spec`` contract),
  warm the new buckets, then swap the ``PlanSet`` atomically between
  bucket dispatches — zero dropped or hung requests. A
  ``StalePlanError`` after a weight refresh is thereby a recoverable
  event: rebuild through ``reload`` instead of dying.
- **Degradation** rides the server's per-bucket kernel fallback
  (``fallback=`` / ``demote_after`` / ``probe_every``); the supervisor
  surfaces demoted buckets in :meth:`health` and rebuilds the fallback
  closures on reload via ``fallback_builder``.

The clock and RNG are injectable so the backoff/breaker logic is
unit-testable without real sleeps (the §14 ``MicroBatcher`` style); the
blocking waits go through ``threading.Event`` so :meth:`stop` — which is
idempotent — interrupts a backoff immediately instead of hanging, and
cancels any crash-stranded futures typed.
"""
from __future__ import annotations

import random
import threading
import time
from typing import Callable, List, Optional

from repro.launch.server import CNNServer, ServerCrashed


class Supervisor:
    """Restart/reload/degradation lifecycle around one ``CNNServer``.

    >>> srv = CNNServer(plan_set, max_wait_ms=5.0)
    >>> sup = Supervisor(srv, rebuild=lambda tree: model.plan_set(tree,
    ...                  max_batch=8), template=qparams)
    >>> with sup:
    ...     sup.warmup()
    ...     fut = sup.submit(x)            # delegates to the server
    ...     sup.reload(ckpt_dir)           # hot swap, zero dropped
    >>> sup.health()["status"], sup.stats.restarts

    Parameters
    ----------
    server:
        The ``CNNServer`` to own. Its ``on_crash`` seam is claimed.
    max_restarts / window_s:
        Circuit breaker: more than ``max_restarts`` crashes within a
        sliding ``window_s`` → stay down, ``health() == 'failed'``.
    backoff_s / backoff_max_s / jitter:
        Restart delay: ``min(backoff_max_s, backoff_s * 2**(n-1))``
        stretched by up to ``jitter`` fraction of seeded randomness —
        bounded, and deterministic for a given seed.
    rebuild:
        ``params_tree -> PlanSet`` for :meth:`reload` (quantize→plan).
    template:
        A params pytree with the checkpoint's structure (what
        ``checkpoint.store.restore`` restores into).
    fallback_builder:
        Optional ``PlanSet -> {bucket: serve}`` rebuilding the §15
        degradation closures for freshly reloaded weights.
    """

    def __init__(self, server: CNNServer, *, max_restarts: int = 5,
                 window_s: float = 30.0, backoff_s: float = 0.05,
                 backoff_max_s: float = 2.0, jitter: float = 0.25,
                 rebuild: Optional[Callable] = None, template=None,
                 fallback_builder: Optional[Callable] = None,
                 seed: int = 0, clock: Callable[[], float] = time.monotonic):
        if max_restarts < 1:
            raise ValueError(f"max_restarts must be >= 1, got {max_restarts}")
        if backoff_s < 0 or backoff_max_s < backoff_s:
            raise ValueError(
                f"need 0 <= backoff_s <= backoff_max_s, got "
                f"{backoff_s}/{backoff_max_s}")
        self._srv = server
        server.on_crash = self._on_crash
        self.max_restarts = max_restarts
        self.window_s = float(window_s)
        self.backoff_s = float(backoff_s)
        self.backoff_max_s = float(backoff_max_s)
        self.jitter = float(jitter)
        self._rng = random.Random(seed)
        self._clock = clock
        self._rebuild = rebuild
        self._template = template
        self._fallback_builder = fallback_builder
        self.reload_failures = 0
        self._lock = threading.Lock()
        self._crash_evt = threading.Event()  # a crash awaits the monitor
        self._wake = threading.Event()       # stop() interrupts backoff
        self._pending: Optional[tuple] = None  # (exc, stranded pendings)
        self._crash_times: List[float] = []
        self._restarting = False
        self._failed_reason: Optional[str] = None
        self._stopped = False
        self._monitor: Optional[threading.Thread] = None

    # ------------------------------------------------------- lifecycle
    def start(self) -> "Supervisor":
        if self._monitor is not None:
            raise RuntimeError("supervisor already started")
        self._stopped = False
        self._failed_reason = None
        self._wake.clear()
        self._crash_evt.clear()
        self._srv.start()  # fresh books for the supervised run
        self._monitor = threading.Thread(
            target=self._monitor_loop, name="cnn-serve-supervisor",
            daemon=True)
        self._monitor.start()
        return self

    def stop(self, *, drain: bool = True,
             timeout_s: Optional[float] = None) -> None:
        """Idempotent shutdown: interrupts any restart backoff (no hang),
        cancels crash-stranded futures typed (``CancelledError``), then
        stops the server (draining by default)."""
        with self._lock:
            self._stopped = True
        self._wake.set()
        self._crash_evt.set()  # unblock an idle monitor
        mon, self._monitor = self._monitor, None
        if mon is not None:
            mon.join()
        with self._lock:
            pending, self._pending = self._pending, None
        if pending is not None:  # crash arrived but monitor never took it
            self._srv.cancel_pending(pending[1])
        self._srv.stop(drain=drain, timeout_s=timeout_s)

    def __enter__(self) -> "Supervisor":
        return self.start()

    def __exit__(self, *exc) -> None:
        self.stop()

    # --------------------------------------------------- restart logic
    def _on_crash(self, exc: BaseException, stranded: list) -> None:
        """Server seam (runs on the dying dispatcher thread): park the
        crash + its undispatched requests for the monitor and return
        immediately."""
        with self._lock:
            self._pending = (exc, list(stranded))
            self._restarting = True
        self._crash_evt.set()

    def _next_backoff(self, attempt: int) -> float:
        """Bounded exponential backoff with deterministic jitter:
        ``min(backoff_max_s, backoff_s * 2**(attempt-1))`` stretched by
        up to ``jitter`` fraction. ``attempt`` is 1-based."""
        base = min(self.backoff_max_s, self.backoff_s * 2 ** max(attempt - 1, 0))
        return base * (1.0 + self.jitter * self._rng.random())

    def _breaker_open(self, now: float) -> bool:
        """Crash-loop circuit breaker: True when the crash just recorded
        is the ``max_restarts + 1``-th inside the sliding window."""
        self._crash_times = [t for t in self._crash_times
                             if now - t <= self.window_s]
        return len(self._crash_times) > self.max_restarts

    def _monitor_loop(self) -> None:
        while True:
            self._crash_evt.wait()
            with self._lock:
                if self._stopped:
                    return
                self._crash_evt.clear()
                taken, self._pending = self._pending, None
            if taken is None:
                continue
            exc, stranded = taken
            now = self._clock()
            self._crash_times.append(now)
            if self._breaker_open(now):
                reason = (f"crash loop: {len(self._crash_times)} crashes "
                          f"within {self.window_s}s (last: {exc!r}) — "
                          "circuit breaker open, staying down")
                err = ServerCrashed(reason)
                err.__cause__ = exc if isinstance(exc, Exception) else None
                with self._lock:
                    self._failed_reason = reason
                    self._restarting = False
                self._srv.fail_pending(stranded, err)
                continue  # stay alive for stop(); server stays down
            delay = self._next_backoff(len(self._crash_times))
            if self._wake.wait(delay):  # stop() landed during backoff
                self._srv.cancel_pending(stranded)
                return
            try:
                self._srv.stop(drain=False)  # reap the dead dispatcher thread
                if stranded:
                    # requeue BEFORE the new dispatcher thread exists: an
                    # immediate re-crash then re-strands them through
                    # on_crash instead of losing them mid-handoff
                    self._srv.requeue(stranded)
                self._srv.start(fresh_stats=False)
                with self._lock:
                    self._srv.stats.restarts += 1
                    self._restarting = False
                faults = getattr(self._srv, "_faults", None)
                if faults is not None and hasattr(faults, "on_restart"):
                    faults.on_restart(self._srv.stats.restarts)
            except Exception as e:  # noqa: BLE001 — restart itself failed
                reason = f"restart failed: {e!r}"
                err = ServerCrashed(reason)
                err.__cause__ = e
                with self._lock:
                    self._failed_reason = reason
                    self._restarting = False
                self._srv.fail_pending(stranded, err)

    # ------------------------------------------------------ hot reload
    def reload(self, ckpt_dir, *, step: Optional[int] = None,
               fallback: bool = False):
        """Verified checkpoint restore → rebuild → warm → atomic swap.

        Everything up to the swap runs on the *caller's* thread: the
        dispatcher keeps serving the old plan throughout, and any
        failure — :class:`~repro.checkpoint.store.CorruptCheckpointError`
        from verification, a rebuild/warmup error, a sample-spec
        mismatch — leaves the old plan serving (the swap never happens)
        and re-raises typed. ``fallback=True`` walks back to the newest
        verifiable checkpoint step. Returns ``(step, fingerprint)`` of
        what is now serving."""
        if self._rebuild is None or self._template is None:
            raise RuntimeError(
                "reload needs Supervisor(rebuild=..., template=...)")
        from repro.checkpoint.store import restore

        old = self._srv.plan_set
        try:
            tree, manifest = restore(ckpt_dir, self._template, step=step,
                                     fallback=fallback)
            new_set = self._srv.for_mesh(self._rebuild(tree))
            if (old.sample_spec is not None
                    and new_set.sample_spec != old.sample_spec):
                raise ValueError(
                    f"reloaded plan sample spec {new_set.sample_spec} != "
                    f"serving admission contract {old.sample_spec}")
            # warm every bucket off the dispatcher thread so the swap
            # lands pre-compiled (zero mid-traffic traces)
            new_set.warmup(put=getattr(self._srv, "_put", None))
            fb = (self._fallback_builder(new_set)
                  if self._fallback_builder is not None else None)
            self._srv.swap_plan_set(new_set, fallback=fb)
        except Exception:
            with self._lock:
                self.reload_failures += 1
            raise  # old plan still serving — reload is all-or-nothing
        return manifest["step"], new_set.fingerprint

    # ------------------------------------------------------ delegation
    @property
    def server(self) -> CNNServer:
        return self._srv

    @property
    def stats(self):
        """The supervised run's books — one ``ServerStats`` spanning
        every restart (``assert_accounting`` stays exact)."""
        return self._srv.stats

    @property
    def restarts(self) -> int:
        return self._srv.stats.restarts

    @property
    def retraces_after_warmup(self) -> int:
        return self._srv.retraces_after_warmup

    def submit(self, x, **kw):
        return self._srv.submit(x, **kw)

    def warmup(self, *a, **kw):
        return self._srv.warmup(*a, **kw)

    def request_timeout_s(self, **kw) -> float:
        return self._srv.request_timeout_s(**kw)

    def health(self) -> dict:
        """The server's §14 snapshot extended with the §15 lifecycle:
        ``'restarting'`` while a crash is between backoff and restart,
        ``'failed'`` (+ ``reason``) once the circuit breaker opens, plus
        the ``restarts``/``requeued`` counters and demoted buckets."""
        base = self._srv.health()
        with self._lock:
            failed = self._failed_reason
            restarting = self._restarting
            stopped = self._stopped
        if failed is not None:
            base["status"] = "failed"
            base["reason"] = failed
        elif restarting:
            base["status"] = "restarting"
        elif stopped and self._monitor is None:
            base["status"] = "stopped"
        base["restarts"] = self._srv.stats.restarts
        base["requeued"] = self._srv.stats.requeued
        base["reloads"] = self._srv.stats.reloads
        base["reload_failures"] = self.reload_failures
        return base
