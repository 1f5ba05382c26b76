"""Continuous-batching CNN serving tier (DESIGN.md §11, §14).

The pipeline is **admission → queue → bucketer → (sharded) frozen-plan
dispatch**:

- :class:`CNNServer` owns a thread-safe request queue. ``submit(x)``
  (``x``: ``(n, H, W, C)``, any ``n ≥ 1``) returns a
  ``concurrent.futures.Future`` that resolves to that request's logits.
- A dispatcher thread aggregates requests with :class:`MicroBatcher`:
  flush as soon as ``max_batch`` samples are pending, or when the oldest
  pending request has waited ``max_wait_ms`` — the classic
  latency/throughput knob pair of a continuous-batching server.
- Each aggregated batch is served through a
  :class:`~repro.models.plan.PlanSet`: pad up to the nearest batch-size
  bucket, launch that bucket's pre-compiled frozen plan, and hand the
  launched batch to a completion thread, which fetches the logits, slices
  the padding off, and scatters the per-request slices back into the
  futures. The dispatcher launches the next batch while the one before
  it is still on the device: at most two batches are launched but not
  yet fetched, so the host's work on one batch overlaps the device's
  work on the other.
  Because every bucket was compiled at warmup, sustained variable load
  runs **zero retraces** — a contract the server *measures* (plans count
  their traces) rather than assumes, and bit-identical to serving every
  request alone (batch rows are independent end to end).
- With a device mesh (``mesh=``, e.g. ``launch.mesh.make_production_mesh``
  / ``make_test_mesh``), each padded bucket is placed with the batch-axis
  ``NamedSharding`` from ``sharding.rules.cnn_serve_rules`` +
  ``data_pspec`` before dispatch, so the plan's jit partitions the batch
  data-parallel across the 'data' (and 'pod') axes; every bucket is a
  multiple of the DP degree by construction (``make_buckets(dp=)``), so
  the padded batch always shards evenly and each device runs the same
  staged program on its shard.

The robustness layer (DESIGN.md §14) makes the tier degrade gracefully
instead of being fast only on the happy path:

- **Admission control**: ``max_queue`` bounds in-system samples. Over
  it, ``shed='reject'`` raises :class:`Overloaded` (carrying a
  retry-after derived from the *measured* bucket service time) and
  ``shed='block'`` applies backpressure. Every request is validated
  against the plan set's per-sample spec (shape / dtype / finiteness)
  at ``submit`` — a malformed request is rejected alone
  (:class:`InvalidRequest`) instead of poisoning a co-batch.
- **Deadlines**: ``submit(x, deadline_s=...)``. The dispatcher subtracts
  the measured service estimate when computing flush deadlines (so a
  tight-deadline request flushes early enough to make it) and fails
  already-expired requests with :class:`DeadlineExceeded` *before*
  wasting a bucket dispatch on them.
- **Blast-radius isolation**: when a batch dispatch raises, the batch is
  **bisected** — each half re-dispatches independently (each half pads
  to an already-warmed bucket, so isolation adds zero retraces) until
  exactly the poison request carries the exception and every innocent
  co-batched request completes with logits bit-identical to a
  fault-free run. Non-finite logits fail only the offending request
  (:class:`NumericalFault`), not its batch.
- **Supervision**: a dispatcher *crash* (not just a dispatch error)
  fails every pending future with :class:`ServerCrashed` instead of
  stranding waiters; :meth:`CNNServer.health` reports
  ready/degraded/stopped; :meth:`CNNServer.stop` takes a drain
  ``timeout_s``; restarting after ``stop()`` resets the run's stats so
  the accounting identity and the zero-retrace snapshot stay valid.
- **Fault hooks**: ``faults=`` installs a deterministic injector
  (:class:`repro.launch.faults.FaultInjector`) at four seams —
  ``on_tick`` (dispatcher kill), ``pre_dispatch`` (plan exception),
  ``pre_serve`` (slow plan), ``post_serve`` (NaN activations) — so the
  chaos suite never monkeypatches internals.

:class:`ServerStats` closes the books on every offered sample:
``completed + rejected + failed + expired == offered`` is an asserted
invariant once the server has stopped.

The load-generator helpers (:func:`poisson_arrivals`,
:func:`burst_arrivals`) live here too so ``benchmarks/bench_serve.py``
and ``repro.launch.serve --server`` drive identical traffic shapes.
"""
from __future__ import annotations

import dataclasses
import functools
import gc
import queue as _queue
import sys
import threading
import time
from concurrent.futures import Future
from typing import Callable, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation


# ------------------------------------------------------- typed failures
class ServeError(RuntimeError):
    """Base of every typed serving-tier failure (DESIGN.md §14)."""


class InvalidRequest(ServeError, ValueError):
    """Rejected at admission: the request does not match the plan's
    per-sample spec (shape / dtype / finiteness) or is structurally
    malformed. Fails only the offending request — it never reaches a
    co-batch."""


class Overloaded(ServeError):
    """Shed at admission: the bounded queue is full (``shed='reject'``).

    ``retry_after_s`` estimates when capacity frees up, derived from the
    measured bucket service time and the current backlog depth."""

    def __init__(self, msg: str, *, retry_after_s: float):
        super().__init__(msg)
        self.retry_after_s = retry_after_s


class DeadlineExceeded(ServeError):
    """The request's ``deadline_s`` passed while it was still queued; it
    was failed before wasting a bucket dispatch."""


class NumericalFault(ServeError):
    """This request's logits came back non-finite; co-batched requests
    were unaffected (batch rows are independent)."""


class ServerCrashed(ServeError):
    """The dispatcher thread itself died; pending futures are failed
    with this instead of stranding their waiters."""


# ------------------------------------------------------------- load gen
def poisson_arrivals(rate_rps: float, n: int, *, seed: int = 0) -> np.ndarray:
    """``n`` arrival offsets (seconds, ascending from ~0) of a Poisson
    process at ``rate_rps`` requests/s — the memoryless steady-traffic
    model; inter-arrival gaps are iid exponential."""
    if rate_rps <= 0:
        raise ValueError(f"rate_rps must be > 0, got {rate_rps}")
    rng = np.random.default_rng(seed)
    return np.cumsum(rng.exponential(1.0 / rate_rps, size=n))


def burst_arrivals(n: int, *, burst: int, gap_s: float,
                   start: float = 0.0) -> np.ndarray:
    """``n`` arrival offsets in back-to-back bursts of ``burst`` requests
    (all at the same instant) separated by ``gap_s`` seconds — the
    worst case for a batcher: idle, then a queue-depth spike."""
    if burst < 1:
        raise ValueError(f"burst must be >= 1, got {burst}")
    return np.asarray([start + (i // burst) * gap_s for i in range(n)])


# ----------------------------------------------------------- validation
def validate_request(x, sample_spec: Tuple[Tuple[int, ...], str],
                     *, check_finite: bool = True) -> None:
    """Admission-time request validation against a plan's per-sample spec
    (``(shape_sans_batch, dtype_name)`` — see ``ModelPlan.sample_spec``).

    Raises :class:`InvalidRequest` on shape or dtype mismatch, and — for
    floating inputs — on any non-finite value, so a NaN/Inf request is
    rejected alone instead of poisoning every co-batched request's
    output. Shared by ``CNNServer.submit`` and the LM plan CLI path.
    """
    shape, dtype = sample_spec
    if tuple(x.shape[1:]) != tuple(shape):
        raise InvalidRequest(
            f"request sample shape {tuple(x.shape[1:])} != plan spec "
            f"{tuple(shape)}")
    if np.dtype(x.dtype) != np.dtype(dtype):
        raise InvalidRequest(
            f"request dtype {np.dtype(x.dtype).name} != plan spec {dtype}")
    if check_finite and np.issubdtype(np.dtype(dtype), np.floating):
        if not np.isfinite(np.asarray(x)).all():
            raise InvalidRequest("request contains non-finite values")


# ------------------------------------------------------------ batching
@dataclasses.dataclass
class _Pending:
    """One queued request: its samples, arrival stamp, result future,
    and (optionally) the absolute monotonic deadline it must meet."""

    x: jax.Array
    n: int
    arrival: float
    future: Future
    deadline: Optional[float] = None


class MicroBatcher:
    """Pure aggregation logic (no threads, injectable clock — unit-testable).

    Accumulates pending requests until either ``max_batch`` samples are
    waiting (flush immediately) or the oldest has waited ``max_wait_s``
    (flush what's there). Requests are never split across batches: a
    request that would overflow the current batch flushes the batch
    first; a single request larger than ``max_batch`` becomes its own
    batch (``PlanSet.serve`` chunks it at the largest bucket).

    Per-request deadlines tighten the flush time: :meth:`deadline`
    returns the earlier of the max-wait flush and the tightest pending
    request deadline *minus the caller's service estimate* — queue wait
    is subtracted from the budget, so a request with a deadline flushes
    early enough to still complete in time rather than expiring in the
    batcher.
    """

    def __init__(self, max_batch: int, max_wait_s: float):
        if max_batch < 1:
            raise ValueError(f"max_batch must be >= 1, got {max_batch}")
        if max_wait_s < 0:
            raise ValueError(f"max_wait_s must be >= 0, got {max_wait_s}")
        self.max_batch = max_batch
        self.max_wait_s = max_wait_s
        self._pending: List[_Pending] = []
        self._count = 0

    def __len__(self) -> int:
        return self._count

    def add(self, p: _Pending) -> List[List[_Pending]]:
        """Queue one request; return the batches (0, 1 or 2) it flushed."""
        out = []
        if self._pending and self._count + p.n > self.max_batch:
            out.append(self.take())
        self._pending.append(p)
        self._count += p.n
        if self._count >= self.max_batch:
            out.append(self.take())
        return out

    def deadline(self, service_est_s: float = 0.0) -> Optional[float]:
        """Absolute time the pending set must flush by: oldest arrival +
        max-wait, tightened by any request deadline less the expected
        service time (``service_est_s``, the dispatcher's measured
        bucket-time estimate)."""
        if not self._pending:
            return None
        dl = self._pending[0].arrival + self.max_wait_s
        for p in self._pending:
            if p.deadline is not None:
                dl = min(dl, p.deadline - service_est_s)
        return dl

    def due(self, now: float, service_est_s: float = 0.0) -> bool:
        dl = self.deadline(service_est_s)
        return dl is not None and now >= dl

    def take(self) -> List[_Pending]:
        """Flush everything pending (the dispatcher's max-wait path)."""
        batch, self._pending, self._count = self._pending, [], 0
        return batch


# --------------------------------------------------------------- stats
@dataclasses.dataclass
class ServerStats:
    """Counters a serving run accumulates (read after ``stop()``).

    All request counters are in **samples**. Every offered sample ends
    in exactly one terminal bucket — the accounting identity
    ``completed + rejected + failed + expired == submitted`` (asserted
    by :meth:`assert_accounting` once the server has stopped):

    - ``completed``: served, future resolved with logits.
    - ``rejected``: shed at admission (:class:`Overloaded` under the
      ``reject`` policy) or failed validation (:class:`InvalidRequest`).
    - ``expired``: missed its deadline while queued
      (:class:`DeadlineExceeded`), failed before any dispatch.
    - ``failed``: a dispatch/output fault (poison request, plan
      exception, :class:`NumericalFault`, :class:`ServerCrashed`) or
      cancelled by a non-draining/timed-out ``stop()``.
    """

    submitted: int = 0
    completed: int = 0
    rejected: int = 0
    failed: int = 0
    expired: int = 0
    batches: int = 0
    served_samples: int = 0
    padded_samples: int = 0
    bucket_counts: dict = dataclasses.field(default_factory=dict)
    latencies_s: list = dataclasses.field(default_factory=list)
    first_arrival: Optional[float] = None
    last_done: Optional[float] = None
    warmup_traces: int = 0
    # --- §15 lifecycle counters. The accounting identity spans restarts:
    # a supervised restart keeps these books open (start(fresh_stats=
    # False)), so a sample submitted before a crash and requeued across
    # it is still offered once and lands in exactly one terminal bucket.
    restarts: int = 0     # supervised dispatcher restarts survived
    requeued: int = 0     # samples re-enqueued across a restart
    reloads: int = 0      # hot plan-set swaps (Supervisor.reload)
    demotions: int = 0    # buckets demoted to the ref fallback path
    promotions: int = 0   # buckets re-promoted by a recovery probe
    # --- where host time goes (time.monotonic sums, always on)
    queue_wait_s: float = 0.0   # Σ dispatch start − arrival, per request
    dispatched_requests: int = 0  # requests that reached a dispatch
    warmup_s: float = 0.0       # CNNServer.warmup's plan_set.warmup, all buckets
    gc_collections: int = 0     # garbage-collector passes while running
    gc_s: float = 0.0           # and the seconds they took
    # bucket dispatches launched while an earlier batch was still unfetched
    overlapped_batches: int = 0

    @property
    def accounted(self) -> int:
        return self.completed + self.rejected + self.failed + self.expired

    def accounting_ok(self) -> bool:
        """The identity every stopped run must satisfy: each offered
        sample landed in exactly one terminal counter."""
        return self.accounted == self.submitted

    def assert_accounting(self) -> None:
        assert self.accounting_ok(), (
            f"accounting identity violated: completed {self.completed} + "
            f"rejected {self.rejected} + failed {self.failed} + expired "
            f"{self.expired} = {self.accounted} != offered {self.submitted}")

    def summary(self) -> dict:
        """p50/p99 latency (µs) of completed requests, goodput
        (requests/s over first-arrival → last-completion), shed rate,
        terminal counters, aggregation shape, and the host-time counters
        (queue wait, warm-up, garbage collection)."""
        lat_us = np.asarray(self.latencies_s, dtype=np.float64) * 1e6
        span = (
            (self.last_done - self.first_arrival)
            if self.completed and self.last_done is not None else 0.0
        )
        return {
            "offered": self.submitted,
            "completed": self.completed,
            "rejected": self.rejected,
            "failed": self.failed,
            "expired": self.expired,
            "accounting_ok": self.accounting_ok(),
            "batches": self.batches,
            "p50_us": round(float(np.percentile(lat_us, 50)), 1) if len(lat_us) else None,
            "p99_us": round(float(np.percentile(lat_us, 99)), 1) if len(lat_us) else None,
            "throughput_rps": round(self.completed / span, 2) if span > 0 else None,
            "shed_rate": round(self.rejected / self.submitted, 4)
            if self.submitted else 0.0,
            "bucket_counts": {str(k): v for k, v in sorted(self.bucket_counts.items())},
            "padded_frac": round(self.padded_samples / self.served_samples, 4)
            if self.served_samples else 0.0,
            "restarts": self.restarts,
            "requeued": self.requeued,
            "reloads": self.reloads,
            "demotions": self.demotions,
            "promotions": self.promotions,
            "queue_wait_s": self.queue_wait_s,
            "dispatched_requests": self.dispatched_requests,
            "warmup_s": self.warmup_s,
            "gc_collections": self.gc_collections,
            "gc_s": self.gc_s,
            "overlapped_batches": self.overlapped_batches,
        }


# --------------------------------------------------------------- server
_STOP = object()
# batches launched but not yet fetched: enough to hide host work shorter
# than a batch's device time; a third would only hold more inputs in HBM
_DEPTH = 2


class CNNServer:
    """Continuous-batching front end over a frozen :class:`PlanSet`.

    >>> plan_set = model.plan_set(qparams, max_batch=8)
    >>> with CNNServer(plan_set, max_wait_ms=5.0, max_queue=64) as srv:
    ...     srv.warmup()                      # buckets from the plan spec
    ...     fut = srv.submit(x1, deadline_s=0.2)   # x1: (1, 32, 32, 3)
    ...     logits = fut.result(timeout=srv.request_timeout_s())
    >>> srv.stats.summary()["p99_us"], srv.retraces_after_warmup  # -> ..., 0

    ``mesh=`` turns on data-parallel dispatch: padded buckets are placed
    with the ``cnn_serve_rules`` batch-axis ``NamedSharding`` and each
    bucket's plan runs under ``shard_map`` on that axis (``PlanSet.shard``),
    so every device serves its own rows (``multi_pod=`` selects the
    ('pod','data') axes). Build the plan set with ``dp=mesh data size``
    so every bucket shards evenly.

    Robustness knobs (DESIGN.md §14): ``max_queue`` bounds admitted
    in-system samples (None = unbounded), ``shed`` picks the overload
    policy (``'reject'`` raises :class:`Overloaded` with a measured
    retry-after; ``'block'`` backpressures the submitting thread),
    ``validate`` checks every request against the plan's sample spec at
    admission, ``check_outputs`` fails individual requests whose logits
    come back non-finite, and ``faults`` installs a deterministic
    injector (``repro.launch.faults``) for chaos testing.

    Two threads serve: the dispatcher admits, batches, assembles and
    launches each batch (``PlanSet.launch``, with the input copy), then
    hands it to the completion thread, which fetches its logits
    (``PlanSet.fetch``) and settles its futures — client callbacks run
    there. The dispatcher launches batch N+1 while batch N is still on
    the device, up to two batches launched but not yet fetched, so
    completion, assembly and the input copy overlap device work. A lone
    request is fetched as soon as its device work ends. A request's
    measured latency (arrival → result ready) includes queueing,
    padding, dispatch, the batch ahead of it on the device, and its own
    device time — what a client would see.
    """

    def __init__(self, plan_set, *, max_batch: Optional[int] = None,
                 max_wait_ms: float = 5.0, mesh=None, multi_pod: bool = False,
                 max_queue: Optional[int] = None, shed: str = "reject",
                 validate: bool = True, check_outputs: bool = True,
                 faults=None, fallback=None, demote_after: int = 2,
                 probe_every: Optional[int] = 4, on_crash=None):
        if shed not in ("reject", "block"):
            raise ValueError(f"shed must be 'reject' or 'block', got {shed!r}")
        if max_queue is not None and max_queue < 1:
            raise ValueError(f"max_queue must be >= 1, got {max_queue}")
        if demote_after < 1:
            raise ValueError(f"demote_after must be >= 1, got {demote_after}")
        if probe_every is not None and probe_every < 2:
            raise ValueError(f"probe_every must be >= 2, got {probe_every}")
        self.plan_set = plan_set
        self.max_batch = int(max_batch or plan_set.buckets[-1])
        self.max_wait_s = float(max_wait_ms) / 1e3
        self.max_queue = max_queue
        self.shed = shed
        self.stats = ServerStats()
        self._validate = validate
        self._check_outputs = check_outputs
        self._faults = faults
        # §15 degradation: per-bucket ref-fallback closures (see
        # models.plan.fallback_closures), demotion threshold in
        # consecutive compiled-dispatch faults, and the recovery-probe
        # period (every Nth dispatch on a demoted bucket retries the
        # compiled path; None disables probing).
        self._fallback = dict(fallback) if fallback is not None else None
        self._demote_after = int(demote_after)
        self._probe_every = probe_every
        self._strikes: dict = {}     # bucket -> consecutive compiled faults
        self._demoted: dict = {}     # bucket -> {'reason', 'dispatches'}
        # §15 supervision: when set, a dispatcher crash hands its
        # admitted-but-undispatched requests to this callback
        # (on_crash(exc, pendings)) instead of failing them — the
        # Supervisor requeues them across the restart. Requests inside a
        # dispatch at crash time always fail typed (at-most-once).
        self.on_crash = on_crash
        # id(p) -> p for the batch the dispatcher is launching (or
        # bisecting); a launched batch leaves it for the completion thread
        self._inflight: dict = {}
        self._put = None
        self._shard = None
        if mesh is not None:
            from jax.sharding import NamedSharding

            from repro.sharding.rules import cnn_serve_rules, data_pspec

            spec = data_pspec(cnn_serve_rules(multi_pod=multi_pod))
            sharding = NamedSharding(mesh, spec)
            self._put = lambda xb: jax.device_put(xb, sharding)
            self._shard = (mesh, spec)
            self.plan_set = self.for_mesh(plan_set)
        self._batcher = MicroBatcher(self.max_batch, self.max_wait_s)
        self._q: _queue.Queue = _queue.Queue()
        self._thread: Optional[threading.Thread] = None
        self._lock = threading.Lock()
        self._space = threading.Condition(self._lock)  # blocks shed='block'
        self._abandon = threading.Event()  # stop(timeout_s=) gave up draining
        self._closed = False
        self._crashed: Optional[BaseException] = None
        self._degraded = False          # last dispatch hit a fault
        self._depth = 0                 # admitted samples not yet resolved
        self._bucket_time_s: Optional[float] = None  # EMA of serve time
        self._last_fetch = 0.0          # when the latest fetch ended
        # how late the dispatcher's timed waits have woken, at most: a
        # deadline flush leaves this much room (at least a GIL switch)
        self._wake_slack_s = sys.getswitchinterval()
        self._unfetched = 0             # batches launched, not yet fetched
        self._slot = threading.Condition(self._lock)  # signals _unfetched < _DEPTH
        self._launched: _queue.Queue = _queue.Queue()  # to the completion thread
        self._completer: Optional[threading.Thread] = None
        self._ran = False
        self._gc_span = None  # the host.gc span of the pass under way
        self._gc_t0 = 0.0

    def for_mesh(self, plan_set):
        """The set this server dispatches for ``plan_set``: sharded over
        the mesh's batch axes (``PlanSet.shard``) when the server has a
        mesh and the set is not sharded yet, else ``plan_set`` itself."""
        if self._shard is None or plan_set.sharded:
            return plan_set
        return plan_set.shard(*self._shard)

    # ------------------------------------------------------- lifecycle
    def start(self, *, fresh_stats: bool = True) -> "CNNServer":
        """Start the dispatcher. ``fresh_stats=False`` is the supervised
        restart path (DESIGN.md §15): the run's books stay open so the
        accounting identity spans the restart — a sample submitted before
        the crash and requeued across it is offered once and terminates
        once. The default resets the run (the §14 operator-restart
        contract: fresh books, re-baselined traces)."""
        if self._thread is not None:
            raise RuntimeError("server already started")
        if self._ran:
            # Restart after stop(): stale stats would double-count the
            # accounting identity and a stale warmup snapshot would
            # corrupt the zero-retrace contract — reset the run and
            # re-baseline traces at the plan set's current count (the
            # buckets stay compiled, so no re-warmup is required).
            keep: List[_Pending] = []
            while True:  # stale sentinels (e.g. stop() after a crash)
                try:
                    item = self._q.get_nowait()
                except _queue.Empty:
                    break
                if isinstance(item, _Pending):
                    # requeued across the restart (§15): the supervisor
                    # re-enqueues crash-stranded requests *before* the new
                    # dispatcher thread exists, so an immediate re-crash
                    # can never lose them mid-handoff.
                    keep.append(item)
            for p in keep:
                self._q.put(p)
            if fresh_stats:
                self.stats = ServerStats()
                self.stats.warmup_traces = self.plan_set.trace_count
            self._batcher = MicroBatcher(self.max_batch, self.max_wait_s)
            with self._lock:
                self._crashed = None
                self._degraded = False
                self._depth = sum(p.n for p in keep)
        self._ran = True
        self._abandon.clear()
        self._closed = False
        self._unfetched = 0  # a crash may have left a launch uncounted
        self._launched = _queue.Queue()
        gc.callbacks.append(self._on_gc)
        self._completer = threading.Thread(
            target=self._complete_loop, name="cnn-serve-complete", daemon=True
        )
        self._completer.start()
        self._thread = threading.Thread(
            target=self._loop, name="cnn-serve-dispatch", daemon=True
        )
        self._thread.start()
        return self

    def stop(self, *, drain: bool = True, timeout_s: Optional[float] = None) -> None:
        """Stop the dispatcher; ``drain=True`` (default) serves whatever
        is still queued first, so every submitted future resolves.
        ``timeout_s`` bounds the drain: past it, remaining requests are
        cancelled (their waiters get ``CancelledError``, never a hang)."""
        if self._thread is None:
            return
        with self._lock:
            self._closed = True  # reject new submits racing the sentinel
            self._q.put((_STOP, drain))
            self._space.notify_all()  # wake blocked submitters to fail fast
        self._thread.join(timeout_s)
        if self._thread.is_alive():
            self._abandon.set()  # drain loop cancels the rest and exits
            self._thread.join()
        self._thread = None
        gc.callbacks.remove(self._on_gc)

    def __enter__(self) -> "CNNServer":
        return self.start()

    def __exit__(self, *exc) -> None:
        self.stop()

    # ------------------------------------------------------- hot path
    def warmup(self, sample_shape: Optional[Sequence[int]] = None,
               dtype=jnp.float32) -> int:
        """Compile every bucket (through the mesh sharding, when set),
        seed the measured service-time estimate with one timed
        largest-bucket dispatch, and snapshot the trace count — the
        baseline of the zero-retrace contract
        (:attr:`retraces_after_warmup`). ``sample_shape`` defaults to
        the plan set's own sample spec."""
        if sample_shape is None and self.plan_set.sample_spec is not None:
            sample_shape, dtype = self.plan_set.sample_spec
        t0 = time.monotonic()
        self.plan_set.warmup(tuple(sample_shape), dtype, put=self._put)
        self.stats.warmup_s += time.monotonic() - t0
        cap = self.plan_set.buckets[-1]
        xb = np.zeros((cap,) + tuple(sample_shape), dtype)
        t0 = time.monotonic()
        self.plan_set.serve(xb, put=self._put)  # warmed: no new trace
        self._note_service_time(t0, time.monotonic())
        self.stats.warmup_traces = self.plan_set.trace_count
        return self.stats.warmup_traces

    @property
    def retraces_after_warmup(self) -> int:
        return self.plan_set.trace_count - self.stats.warmup_traces

    def submit(self, x, *, deadline_s: Optional[float] = None) -> Future:
        """Enqueue one request (``x``: ``(n, ...)`` with ``n ≥ 1``
        samples, numpy preferred — jax inputs are copied to host at
        dispatch); returns the future of its ``(n, num_classes)`` logits
        as numpy, already computed when the future resolves.

        ``deadline_s`` (relative seconds) bounds total time-in-system:
        a request still queued past it fails with
        :class:`DeadlineExceeded` before any dispatch. Raises
        :class:`InvalidRequest` on spec validation failure and
        :class:`Overloaded` when the bounded queue sheds (both typed,
        both counted against the accounting identity)."""
        if x.ndim < 2 or x.shape[0] < 1:
            raise InvalidRequest(
                f"request must be (n, ...) with n >= 1: {x.shape}")
        n = int(x.shape[0])
        with TraceAnnotation("serve.submit", samples=n):
            now = time.monotonic()
            with self._lock:
                if self._crashed is not None:
                    raise ServerCrashed(
                        f"server crashed: {self._crashed!r} (restart with start())")
                if self._thread is None or self._closed:
                    raise RuntimeError(
                        "server is not running (use `with CNNServer(...)`)")
                self.stats.submitted += n  # offered, whatever happens next
                if self.stats.first_arrival is None:
                    self.stats.first_arrival = now
            try:
                if deadline_s is not None and deadline_s <= 0:
                    raise InvalidRequest(f"deadline_s must be > 0: {deadline_s}")
                if self._validate and self.plan_set.sample_spec is not None:
                    validate_request(x, self.plan_set.sample_spec)
            except InvalidRequest:
                with self._lock:
                    self.stats.rejected += n  # rejected alone — no co-batch harm
                raise
            fut: Future = Future()
            p = _Pending(x=x, n=n, arrival=now, future=fut,
                         deadline=None if deadline_s is None else now + deadline_s)
            with self._lock:
                if self.max_queue is not None and self._depth + n > self.max_queue:
                    if self.shed == "reject":
                        self.stats.rejected += n
                        raise Overloaded(
                            f"queue full ({self._depth}/{self.max_queue} samples)",
                            retry_after_s=self._retry_after_locked())
                    while (self._depth + n > self.max_queue
                           and not self._closed and self._crashed is None):
                        self._space.wait()
                    if self._closed or self._crashed is not None:
                        self.stats.rejected += n
                        raise RuntimeError("server stopped while backpressured")
                self._depth += n
                self._q.put(p)  # inside the lock: nothing can trail a crash drain
            return fut

    def serve_batch(self, x):
        """Synchronous bucketed serve (no queue): pad → bucket plan →
        slice, through the mesh sharding when set. The dispatcher and
        direct callers (tests/bench baselines) share this one path
        (:meth:`_launch_plans`, then ``PlanSet.fetch``), including the
        §15 per-bucket demotion routing."""
        return self.plan_set.fetch(self._launch_plans(x))

    def _launch_plans(self, x):
        """``PlanSet.launch`` of ``x`` on the serving set, read once: a
        batch runs wholly on the set that launched it, whatever a
        concurrent ``swap_plan_set`` does."""
        ps = self.plan_set
        return ps.launch(x, put=self._put, on_dispatch=self._record,
                         dispatch=functools.partial(self._bucket_dispatch, ps))

    def requeue(self, pendings: List[_Pending]) -> int:
        """Re-enqueue requests a crash handed back (``on_crash``) after a
        supervised restart — the §15 at-most-once path for requests that
        were admitted but never inside a dispatch. They are *not*
        re-counted as submitted (their offer already happened); the
        ``requeued`` counter keeps the cross-restart books exact. Returns
        the number of samples requeued.

        Callable on a running server *or* on a reaped one (after
        ``stop()``, before the restarting ``start()``) — the supervisor
        uses the latter so the requests sit in the queue before the new
        dispatcher thread exists, closing the window where an immediate
        re-crash could lose them mid-handoff."""
        total = 0
        with self._lock:
            if self._thread is not None and (self._closed
                                             or self._crashed is not None):
                raise RuntimeError(
                    "cannot requeue into a crashed/closing server "
                    "(reap the dispatcher with stop() first)")
            for p in pendings:
                self.stats.requeued += p.n
                self._depth += p.n
                total += p.n
                self._q.put(p)
        return total

    def fail_pending(self, pendings: List[_Pending], exc: Exception) -> None:
        """Terminal-fail requests a crash handed back — the Supervisor's
        path when the circuit breaker keeps the server down. Books stay
        exact (each sample lands in ``failed``)."""
        for p in pendings:
            self._fail(p, exc, kind="failed")

    def cancel_pending(self, pendings: List[_Pending]) -> None:
        """Cancel requests a crash handed back — the Supervisor's path
        when ``stop()`` lands during restart backoff. Waiters get
        ``CancelledError`` (typed, never a hang); books stay exact."""
        for p in pendings:
            self._cancel(p)

    def swap_plan_set(self, new_set, *, fallback=None) -> None:
        """Atomically replace the serving :class:`PlanSet` (the §15 hot
        reload). The dispatcher reads ``plan_set`` once per batch, so the
        swap lands *between* batch launches: launched batches finish
        on the old plans (still alive, still compiled), every later batch
        dispatches the new ones — zero dropped or hung requests. The
        caller must pass an already-warmed set (``Supervisor.reload``
        warms off the dispatcher thread); the trace baseline re-anchors
        at the new set's count so the zero-retrace contract carries over.
        Demotion state and fallback closures are rebuilt per swap (they
        are pinned to the old weights)."""
        if tuple(new_set.buckets) != tuple(self.plan_set.buckets):
            raise ValueError(
                f"swap buckets {new_set.buckets} != serving ladder "
                f"{self.plan_set.buckets}")
        if (self.plan_set.sample_spec is not None
                and new_set.sample_spec != self.plan_set.sample_spec):
            raise ValueError(
                f"swap sample spec {new_set.sample_spec} != admission "
                f"contract {self.plan_set.sample_spec}")
        new_set = self.for_mesh(new_set)
        with self._lock:
            self.plan_set = new_set
            self.stats.warmup_traces = new_set.trace_count
            self.stats.reloads += 1
            self._fallback = dict(fallback) if fallback is not None else None
            self._strikes.clear()
            self._demoted.clear()

    # ------------------------------------------- §15 bucket degradation
    def _bucket_dispatch(self, ps, b: int, xb):
        """Per-bucket dispatch with kernel-fallback demotion: a healthy
        bucket runs its compiled plan; ``demote_after`` consecutive
        compiled-dispatch faults demote the bucket to its ref fallback
        closure (requests keep completing — bit-compatible by
        construction); every ``probe_every``-th dispatch on a demoted
        bucket retries the compiled path and re-promotes on success."""
        with self._lock:
            dem = self._demoted.get(b)
            probe = False
            if dem is not None:
                dem["dispatches"] += 1
                probe = (self._probe_every is not None
                         and dem["dispatches"] % self._probe_every == 0)
        if dem is not None and not probe:
            return self._fallback[b](xb)
        try:
            if self._faults is not None:
                self._faults.pre_bucket(b)  # compiled-backend fault seam
            y = ps.plans[b].serve(xb)
        except Exception as e:  # noqa: BLE001 — strike, demote, or bubble
            if dem is not None:  # failed probe: stay demoted, keep serving
                return self._fallback[b](xb)
            if self._strike(b, e):
                return self._fallback[b](xb)  # demoted now: rescue the batch
            raise  # pre-demotion: bisect isolation handles the batch
        if dem is not None:
            self._promote(b)
        else:
            with self._lock:
                self._strikes.pop(b, None)  # a clean dispatch resets strikes
        return y

    def _strike(self, b: int, exc: Exception) -> bool:
        """One compiled-dispatch fault against bucket ``b``; demotes at
        the threshold when a fallback closure exists. True = demoted."""
        with self._lock:
            if b in self._demoted:
                return False
            k = self._strikes.get(b, 0) + 1
            self._strikes[b] = k
            if (self._fallback is not None and b in self._fallback
                    and k >= self._demote_after):
                self._demoted[b] = {
                    "reason": f"{type(exc).__name__}: {exc}",
                    "dispatches": 0,
                }
                self._strikes.pop(b, None)
                self.stats.demotions += 1
                return True
        return False

    def _promote(self, b: int) -> None:
        with self._lock:
            if self._demoted.pop(b, None) is not None:
                self._strikes.pop(b, None)
                self.stats.promotions += 1

    def demoted_buckets(self) -> dict:
        """``{bucket: reason}`` for buckets serving on the ref fallback."""
        with self._lock:
            return {b: d["reason"] for b, d in sorted(self._demoted.items())}

    # ---------------------------------------------------------- health
    def health(self) -> dict:
        """Liveness snapshot: ``status`` is ``'ready'`` (dispatching,
        last dispatch clean, queue below capacity), ``'degraded'``
        (running, but the last dispatch hit a fault, the queue is at
        capacity and shedding, or a bucket is demoted to its ref
        fallback — ``demoted`` carries ``{bucket: reason}``), or
        ``'stopped'`` (never started, stopped, or crashed — ``crashed``
        distinguishes)."""
        with self._lock:
            running = (self._thread is not None and not self._closed
                       and self._crashed is None)
            at_capacity = (self.max_queue is not None
                           and self._depth >= self.max_queue)
            demoted = {b: d["reason"] for b, d in sorted(self._demoted.items())}
            if not running:
                status = "stopped"
            elif self._degraded or at_capacity or demoted:
                status = "degraded"
            else:
                status = "ready"
            return {
                "status": status,
                "crashed": self._crashed is not None,
                "queue_depth": self._depth,
                "max_queue": self.max_queue,
                "service_estimate_s": self._bucket_time_s,
                "demoted": demoted,
            }

    def service_estimate_s(self) -> Optional[float]:
        """EMA of measured bucket dispatch time (seeded by warmup)."""
        with self._lock:
            return self._bucket_time_s

    def request_timeout_s(self, *, slack_buckets: float = 8.0,
                          floor_s: float = 5.0) -> float:
        """Client-side ``Future.result`` timeout derived from the
        server's own config instead of a hardcoded constant: worst-case
        backlog ahead (``max_queue`` when bounded, else the current
        depth) in buckets plus ``slack_buckets``, at the measured bucket
        time, plus the max-wait — floored so an unwarmed server still
        gets a sane value."""
        with self._lock:
            bt = self._bucket_time_s
            depth = self.max_queue if self.max_queue is not None else self._depth
        bt = bt if bt is not None else 1.0
        buckets = -(-max(depth, 0) // self.max_batch) + slack_buckets
        return max(floor_s, self.max_wait_s + buckets * bt)

    # ------------------------------------------------------- internals
    def _retry_after_locked(self) -> float:
        """Overload retry-after: backlog depth in buckets × measured
        bucket time (max-wait floor when nothing is measured yet)."""
        bt = self._bucket_time_s or self.max_wait_s
        buckets_ahead = max(1, -(-self._depth // self.max_batch))
        return self.max_wait_s + buckets_ahead * bt

    def _note_service_time(self, launched: float, done: float) -> None:
        """One batch's service time into the EMA: from its launch, or from
        the previous batch's fetch where that ended later, to its own
        fetch. With batches overlapped that is the time between
        successive completions, one batch's device time, not two."""
        with self._lock:
            dt = done - max(launched, self._last_fetch)
            self._last_fetch = done
            bt = self._bucket_time_s
            self._bucket_time_s = dt if bt is None else 0.8 * bt + 0.2 * dt

    def _flush_lead_s(self) -> float:
        """How long before a request's deadline its batch must flush: a
        service time for each batch ahead of it on the device and one for
        its own, plus the latest the dispatcher has woken from a timed
        wait (the flush fires on the dispatcher's own clock)."""
        with self._lock:
            bt = self._bucket_time_s or 0.0
            return bt * (1 + self._unfetched) + self._wake_slack_s

    def _record(self, bucket: int, n_real: int) -> None:
        with self._lock:
            self.stats.batches += 1
            self.stats.served_samples += bucket
            self.stats.padded_samples += bucket - n_real
            self.stats.bucket_counts[bucket] = (
                self.stats.bucket_counts.get(bucket, 0) + 1)

    def _loop(self) -> None:
        try:
            self._loop_inner()
        except BaseException as e:  # noqa: BLE001 — supervised: fail futures
            self._crash(e)
        finally:
            # every launched batch is fetched and settled before the
            # dispatcher thread ends, so a joined dispatcher leaves none
            self._launched.put(None)
            self._completer.join()

    def _loop_inner(self) -> None:
        stop = None
        while stop is None:
            timeout = None
            est = self._flush_lead_s()
            dl = self._batcher.deadline(est)
            if dl is not None:
                timeout = max(0.0, dl - time.monotonic())
            try:
                with TraceAnnotation("serve.wait"):
                    items = [self._q.get(timeout=timeout)]
            except _queue.Empty:
                items = []  # max-wait expired with nothing new queued
                if timeout:
                    late = time.monotonic() - dl
                    with self._lock:
                        self._wake_slack_s = max(self._wake_slack_s, late)
            # Greedily drain whatever arrived while the last batch was in
            # flight: a backlog coalesces into full buckets here instead
            # of degenerating into max-wait-expired singles.
            while True:
                try:
                    items.append(self._q.get_nowait())
                except _queue.Empty:
                    break
            if self._faults is not None and items:
                try:
                    self._faults.on_tick(len(items))  # dispatcher-kill seam
                except BaseException:
                    for it in items:  # keep them failable by _crash
                        self._q.put(it)
                    raise
            for item in items:
                if isinstance(item, tuple) and item[0] is _STOP:
                    # submit() rejects after _closed, so nothing trails
                    # the sentinel — finish feeding what preceded it.
                    stop = item
                    continue
                for batch in self._batcher.add(item):
                    self._dispatch(batch)
            if stop is None and self._batcher.due(time.monotonic(), est):
                self._dispatch(self._batcher.take())
        remainder = self._batcher.take()
        if stop[1]:  # drain: serve what's left so every future resolves
            while remainder and not self._abandon.is_set():
                take, nn = [], 0
                while remainder and (not take
                                     or nn + remainder[0].n <= self.max_batch):
                    p = remainder.pop(0)
                    take.append(p)
                    nn += p.n
                self._dispatch(take)
        for p in remainder:  # non-drain or abandoned drain: cancel
            self._cancel(p)

    def _release_slot(self) -> None:
        with self._lock:
            self._unfetched -= 1
            self._slot.notify()

    def _dispatch(self, batch: List[_Pending]) -> None:
        """Wait for a launch slot (fewer than ``_DEPTH`` batches
        unfetched), expire what already missed its deadline — *before*
        wasting a bucket dispatch — then launch the survivors and hand
        them to the completion thread."""
        with self._lock:
            while self._unfetched >= _DEPTH:
                self._slot.wait()
            overlapped = self._unfetched > 0
            self._unfetched += 1
        if self._abandon.is_set():  # stop(timeout_s=) gave up: cancel, fast
            self._release_slot()
            for p in batch:
                self._cancel(p)
            return
        now = time.monotonic()
        live = []
        for p in batch:
            if p.deadline is not None and now >= p.deadline:
                self._fail(p, DeadlineExceeded(
                    f"deadline missed by {now - p.deadline:.4f}s after "
                    f"{now - p.arrival:.4f}s queued (never dispatched)"),
                    kind="expired")
            else:
                live.append(p)
        if not live:
            self._release_slot()
            return
        # At-most-once bookkeeping (§15): everything past this line is
        # "inside a dispatch" — if the dispatcher dies before a request
        # reaches a terminal outcome, _crash fails it typed instead of
        # handing it to the supervisor for a requeue (a re-execution could
        # double side effects / double-serve).
        with self._lock:
            for p in live:
                self._inflight[id(p)] = p
                self.stats.queue_wait_s += now - p.arrival
            self.stats.dispatched_requests += len(live)
        with TraceAnnotation("serve.batch", requests=len(live),
                             samples=sum(p.n for p in live)):
            try:
                launched, t0 = self._launch(live)
            except Exception as e:  # noqa: BLE001 — isolate, don't kill the loop
                err = e
            else:
                with self._lock:
                    if overlapped:
                        self.stats.overlapped_batches += len(launched.parts)
                    for p in live:  # the completion thread settles them now
                        self._inflight.pop(id(p), None)
                self._launched.put((live, launched, t0))
                return
        try:
            self._isolate(live, err)
        finally:
            self._release_slot()

    def _complete_loop(self) -> None:
        """The completion thread: fetch each launched batch in launch
        order, free its slot, and settle its futures."""
        while True:
            item = self._launched.get()
            if item is None:
                return
            batch, launched, t0 = item
            try:
                try:
                    y = self._fetch(batch, launched, t0)
                except Exception as e:  # noqa: BLE001 — isolate this batch
                    self._isolate(batch, e)  # its slot held till settled
                    continue
                finally:
                    self._release_slot()
                self._resolve(batch, y)
            except Exception as e:  # noqa: BLE001 — never strand a waiter
                err = ServerCrashed(f"completion failed: {e!r}")
                for p in batch:
                    if not p.future.done():
                        self._fail(p, err, kind="failed")

    def _run(self, batch: List[_Pending]) -> None:
        """One batch start to finish on this thread — launch, fetch,
        settle — bisected on a fault (the isolation path)."""
        with TraceAnnotation("serve.batch", requests=len(batch),
                             samples=sum(p.n for p in batch)):
            try:
                y = self._fetch(batch, *self._launch(batch))
            except Exception as e:  # noqa: BLE001 — isolate, don't kill the loop
                err = e
            else:
                self._resolve(batch, y)
                return
        self._isolate(batch, err)

    def _isolate(self, batch: List[_Pending], err: Exception) -> None:
        """Pin a batch's fault on its poison request(s)."""
        if len(batch) == 1:
            self._fail(p=batch[0], exc=err, kind="failed")
            return
        # Blast-radius isolation: bisect. Each half pads up to an
        # already-warmed bucket, so innocent co-batched requests complete
        # bit-identically to a fault-free run (batch rows are independent)
        # with zero new traces, and recursion pins the exception on exactly
        # the poison request(s).
        mid = (len(batch) + 1) // 2
        self._run(batch[:mid])
        self._run(batch[mid:])

    def _launch(self, batch: List[_Pending]):
        """Assemble one batch and launch its plans, through the fault
        seams; returns the launch and when it started."""
        if self._faults is not None:
            self._faults.pre_dispatch(batch)  # plan-exception seam
        # Host-side assembly (numpy): concatenating/padding/slicing k
        # request arrays as jax ops would XLA-compile a fresh glue op per
        # (k, sizes) signature mid-traffic — a latency spike the warmed
        # bucket plans exist to avoid. As numpy it is a memcpy, and
        # the plan set's host fast path keeps it that way end to end (the
        # only device work is the bucket dispatch).
        with TraceAnnotation("serve.assemble"):
            xs = [np.asarray(p.x) for p in batch]
            xb = xs[0] if len(xs) == 1 else np.concatenate(xs, axis=0)
        if self._faults is not None:
            xb = self._faults.pre_serve(batch, xb)  # slow/NaN seam
        t0 = time.monotonic()
        return self._launch_plans(xb), t0  # numpy in: numpy out at fetch

    def _fetch(self, batch: List[_Pending], launched, t0: float):
        """A launched batch's logits (numpy), through the fault seam."""
        y = self.plan_set.fetch(launched)
        self._note_service_time(t0, time.monotonic())
        if self._faults is not None:
            y = self._faults.post_serve(batch, y)  # NaN-activation seam
        return y

    def _resolve(self, batch: List[_Pending], y) -> None:
        """Slice each request's logits off ``y`` and settle its future
        (client callbacks run here, on the thread that fetched)."""
        with TraceAnnotation("serve.complete", requests=len(batch)):
            done = time.monotonic()
            off = 0
            clean = True
            for p in batch:
                yp = y[off : off + p.n]
                off += p.n
                if (self._check_outputs
                        and np.issubdtype(np.asarray(yp).dtype, np.floating)
                        and not np.isfinite(yp).all()):
                    # fail only the offending request — its co-batch is fine
                    self._fail(p, NumericalFault(
                        f"non-finite logits for request of {p.n} sample(s)"),
                        kind="failed")
                    clean = False
                else:
                    self._complete(p, yp, done)
            if clean:
                with self._lock:
                    self._degraded = False  # a clean batch clears the flag

    # ------------------------------------------------------ host.gc span
    def _on_gc(self, phase: str, info: dict) -> None:
        """``gc.callbacks`` hook, installed while the dispatcher runs:
        each collector pass in the process is a ``host.gc`` span and
        counts into ``gc_collections`` / ``gc_s``. Passes never overlap,
        and each starts and stops on one thread."""
        if phase == "start":
            self._gc_t0 = time.monotonic()
            self._gc_span = TraceAnnotation("host.gc",
                                            generation=info["generation"])
            self._gc_span.__enter__()
        elif self._gc_span is not None:
            self._gc_span.__exit__(None, None, None)
            self._gc_span = None
            self.stats.gc_collections += 1
            self.stats.gc_s += time.monotonic() - self._gc_t0

    # ----------------------------------------------- terminal outcomes
    def _settle_locked(self, p: _Pending) -> None:
        self._inflight.pop(id(p), None)
        self._depth -= p.n
        self._space.notify_all()

    def _complete(self, p: _Pending, y, done: float) -> None:
        with self._lock:
            self._settle_locked(p)
            self.stats.latencies_s.append(done - p.arrival)
            self.stats.completed += p.n
            self.stats.last_done = done
        try:
            p.future.set_result(y)
        except Exception:  # cancelled by a racing stop(): already terminal
            pass

    def _fail(self, p: _Pending, exc: Exception, kind: str) -> None:
        with self._lock:
            self._settle_locked(p)
            setattr(self.stats, kind, getattr(self.stats, kind) + p.n)
            if kind == "failed":
                self._degraded = True
        try:
            p.future.set_exception(exc)
        except Exception:
            pass

    def _cancel(self, p: _Pending) -> None:
        with self._lock:
            self._settle_locked(p)
            self.stats.failed += p.n  # never served; the identity closes
        p.future.cancel()  # waiters get CancelledError, never a hang

    def _crash(self, exc: BaseException) -> None:
        """Supervision: the dispatcher died — fail every pending future
        with :class:`ServerCrashed` instead of stranding their waiters.
        ``submit`` raises the same from then on (until a restart).

        §15 split: requests *inside a dispatch* at crash time — the batch
        the dispatcher was assembling, launching or bisecting — always
        fail typed here (at-most-once — a requeue could silently
        re-execute them), while admitted-but-undispatched requests are
        handed to the ``on_crash`` callback (the Supervisor requeues them
        across the restart) when one is installed, and failed typed
        otherwise. Batches already launched belong to the completion
        thread, which fetches and settles them before the dispatcher
        thread ends: they ran once, and none is requeued."""
        with self._lock:
            self._crashed = exc
            self._closed = True
            self._space.notify_all()
            inflight = list(self._inflight.values())
        err = ServerCrashed(f"dispatcher crashed: {exc!r}")
        err.__cause__ = exc if isinstance(exc, Exception) else None
        for p in inflight:  # at-most-once: never silently re-executed
            self._fail(p, err, kind="failed")
        stranded = self._batcher.take()
        while True:  # submit() enqueues under the lock: nothing can trail
            try:
                item = self._q.get_nowait()
            except _queue.Empty:
                break
            if isinstance(item, tuple) and item[0] is _STOP:
                continue
            stranded.append(item)
        cb = self.on_crash
        if cb is not None and stranded:
            # the undispatched stay pending: depth still counts them, and
            # the supervisor either requeues them (stats.requeued) or
            # fails them itself when the circuit breaker holds the server
            # down. A callback error must never strand a waiter.
            try:
                cb(exc, stranded)
                return
            except Exception:  # noqa: BLE001 — fall through to typed fail
                pass
        elif cb is not None:
            try:
                cb(exc, [])
                return
            except Exception:  # noqa: BLE001
                pass
        for p in stranded:
            self._fail(p, err, kind="failed")


def auto_rate(plan_set, sample_shape: Sequence[int], *, utilization: float = 0.5,
              dtype=jnp.float32, put=None, reps: int = 5) -> Tuple[float, float]:
    """Pick an offered load from measured capacity: times the largest
    bucket's plan (median of ``reps``) and returns ``(rate_rps,
    bucket_us)`` where ``rate_rps = utilization × bucket/bucket_time`` —
    so load runs are self-calibrating across hosts instead of hardcoding
    a requests/s that is idle on one machine and overload on another."""
    from repro.xla_utils import median_time_us

    cap = plan_set.buckets[-1]
    xb = jnp.zeros((cap,) + tuple(sample_shape), dtype)
    if put is not None:
        xb = put(xb)
    us = median_time_us(plan_set.plans[cap].serve, xb, warmup=1, reps=reps)
    return utilization * cap / (us / 1e6), us
