"""Double-buffered dispatch in the serving tier (DESIGN.md §11).

The dispatcher launches batch N+1 before batch N's logits are fetched,
with at most two batches launched and not yet fetched; a completion
thread fetches them in order and settles their futures. On the CPU a
bucket plan finishes in microseconds, so these tests run the plans on a
fake device: each launch queues behind the batches ahead of it for a
fixed time, and fetching its result waits until that time has passed, as
a chip's batch would. The ref-kernel smoke CNN computes the logits.
"""
import dataclasses
import sys
import threading
import time

import jax
import numpy as np
import pytest

from repro.checkpoint.store import save as ckpt_save
from repro.configs import smoke_cnn_config
from repro.launch.server import CNNServer, ServerCrashed, burst_arrivals, \
    poisson_arrivals
from repro.launch.supervisor import Supervisor
from repro.models.cnn import SparseCNN
from repro.models.plan import PlanSet


@pytest.fixture(scope="module")
def served():
    """Ref-kernel quantized model, its params, 12 images, and a
    max_batch=4 plan set (buckets 1, 2, 4)."""
    cfg = dataclasses.replace(
        smoke_cnn_config("sparse-cnn-tiny", sparsity=0.625), kernel_mode="ref"
    )
    model = SparseCNN(cfg)
    params = model.compress(model.init(jax.random.PRNGKey(0)))
    x = jax.random.normal(
        jax.random.PRNGKey(1),
        (12, cfg.image_size, cfg.image_size, cfg.in_channels),
    )
    _, stats = model.apply(params, x[:4], collect_act_stats=True)
    qparams = model.quantize(params, stats)
    plan_set = model.plan_set(qparams, max_batch=4)
    return model, qparams, np.asarray(x), plan_set


class DeviceFault(RuntimeError):
    """What the fake device raises for a batch holding the poison row."""


class _Device:
    """One queue of batches, each taking ``batch_s``; logs every launch
    and every fetch as ``(kind, tag, bucket, thread name, time)``. A batch
    holding the ``poison`` row faults at ``fault_at`` ('launch' or
    'fetch')."""

    def __init__(self, batch_s: float, *, poison=None, fault_at="fetch"):
        self.batch_s = batch_s
        self.poison = poison
        self.fault_at = fault_at
        self.free_at = 0.0
        self.log = []
        self.lock = threading.Lock()

    def note(self, kind, tag, bucket):
        with self.lock:
            self.log.append((kind, tag, bucket,
                             threading.current_thread().name, time.monotonic()))

    def events(self, kind, thread=None):
        with self.lock:
            return [e for e in self.log
                    if e[0] == kind and thread in (None, e[3])]

    def faults(self, xb) -> bool:
        if self.poison is None:
            return False
        return bool(np.all(np.asarray(xb) == self.poison, axis=(1, 2, 3)).any())


class _Result:
    """A launched batch's logits: ready once the device reaches it."""

    def __init__(self, device, y, ready, tag, bucket, fault):
        self.device, self.y, self.ready = device, y, ready
        self.tag, self.bucket, self.fault = tag, bucket, fault

    def __array__(self, dtype=None, copy=None):
        time.sleep(max(0.0, self.ready - time.monotonic()))
        if self.fault:
            raise DeviceFault("device fault surfaced at fetch")
        y = np.asarray(self.y, dtype=dtype)
        self.device.note("fetch", self.tag, self.bucket)
        return y


class _Plan:
    """A bucket plan whose launches run on the fake device."""

    def __init__(self, plan, device, tag):
        self.plan, self.device, self.tag = plan, device, tag

    @property
    def trace_count(self):
        return self.plan.trace_count

    def serve(self, xb):
        dev = self.device
        fault = dev.faults(xb)
        if fault and dev.fault_at == "launch":
            raise DeviceFault("device fault at launch")
        y = self.plan.serve(xb)
        with dev.lock:
            dev.free_at = max(time.monotonic(), dev.free_at) + dev.batch_s
            ready = dev.free_at
        dev.note("launch", self.tag, xb.shape[0])
        return _Result(dev, y, ready, self.tag, xb.shape[0],
                       fault and dev.fault_at == "fetch")


def on_device(ps: PlanSet, device: _Device, tag: str = "a") -> PlanSet:
    return PlanSet(ps.model, ps.fingerprint, ps.buckets,
                   {b: _Plan(p, device, tag) for b, p in ps.plans.items()},
                   ps.sample_spec)


def _wait_for(cond, what, timeout_s=10.0):
    deadline = time.monotonic() + timeout_s
    while not cond():
        assert time.monotonic() < deadline, what
        time.sleep(0.002)


DISPATCH = "cnn-serve-dispatch"


# ------------------------------------------------------------- overlap
def test_backlog_launches_next_before_fetch(served):
    """Three full batches queued at once: each batch after the first is
    launched while the one before it is still on the device, never more
    than two are launched and unfetched, and the logits are the plan
    set's own."""
    _, _, x, ps = served
    dev = _Device(0.2)
    srv = CNNServer(on_device(ps, dev), max_wait_ms=1000.0)
    with srv:
        srv.warmup()
        futs = [srv.submit(x[i : i + 4]) for i in (0, 4, 8)]
        ys = [f.result(timeout=30) for f in futs]
    launches = dev.events("launch", DISPATCH)
    fetches = dev.events("fetch", "cnn-serve-complete")
    assert len(launches) == len(fetches) == 3
    for k in range(2):
        assert launches[k + 1][-1] < fetches[k][-1]  # N+1 before N's fetch
    for launch in launches:
        unfetched = (sum(e[-1] <= launch[-1] for e in launches)
                     - sum(e[-1] <= launch[-1] for e in fetches))
        assert unfetched <= 2
    assert srv.stats.overlapped_batches == 2 == srv.stats.batches - 1
    for i, y in zip((0, 4, 8), ys):
        np.testing.assert_array_equal(y, ps.serve(x[i : i + 4]))
    assert srv.retraces_after_warmup == 0
    srv.stats.assert_accounting()


def test_lone_requests_are_not_deferred(served):
    """Requests sent one at a time, each after the last came back: no
    batch is ever launched behind another, and each answer is what
    ``serve_batch`` gives that request alone."""
    _, _, x, ps = served
    dev = _Device(0.01)
    srv = CNNServer(on_device(ps, dev), max_wait_ms=1.0)
    with srv:
        srv.warmup()
        ys = [srv.submit(x[i : i + 1]).result(timeout=30) for i in range(6)]
    assert srv.stats.batches == 6 and srv.stats.overlapped_batches == 0
    for i, y in enumerate(ys):
        np.testing.assert_array_equal(y, srv.serve_batch(x[i : i + 1]))
    srv.stats.assert_accounting()


@pytest.mark.parametrize("arrivals", ["poisson", "burst"])
def test_overlapped_logits_bit_identical(served, arrivals):
    """Requests of 1–3 images under Poisson and burst arrivals, batched
    and overlapped on the device: every request's logits are
    bit-identical to serving it alone, with zero retraces."""
    _, _, x, ps = served
    n = 24
    at = (poisson_arrivals(400.0, n, seed=3) if arrivals == "poisson"
          else burst_arrivals(n, burst=8, gap_s=0.03))
    rng = np.random.default_rng(5)
    sizes = rng.integers(1, 4, size=n)
    starts = [int(rng.integers(0, len(x) - k + 1)) for k in sizes]
    dev = _Device(0.005)
    srv = CNNServer(on_device(ps, dev), max_wait_ms=2.0)
    with srv:
        srv.warmup()
        t0 = time.monotonic()
        futs = []
        for t, s, k in zip(at, starts, sizes):
            time.sleep(max(0.0, t0 + t - time.monotonic()))
            futs.append(srv.submit(x[s : s + k]))
        ys = [f.result(timeout=30) for f in futs]
    for y, s, k in zip(ys, starts, sizes):
        np.testing.assert_array_equal(y, ps.serve(x[s : s + k]))
    assert srv.retraces_after_warmup == 0
    s = srv.stats.summary()
    assert s["completed"] == s["offered"] == int(sizes.sum())
    srv.stats.assert_accounting()


# -------------------------------------------------------------- faults
@pytest.mark.parametrize("fault_at", ["launch", "fetch"])
def test_device_fault_pinned_on_its_batch(served, fault_at):
    """A device fault in batch N, raised at its launch or at its fetch:
    bisection pins it on the one poisoned request, N's other requests and
    the overlapped batch N+1 complete bit-identical, with no new trace."""
    _, _, x, ps = served
    dev = _Device(0.05, poison=x[1], fault_at=fault_at)
    srv = CNNServer(on_device(ps, dev), max_wait_ms=1000.0)
    with srv:
        srv.warmup()
        futs = [srv.submit(x[i : i + 1]) for i in range(8)]  # two batches of 4
        for i, f in enumerate(futs):
            if i == 1:
                with pytest.raises(DeviceFault):
                    f.result(timeout=30)
            else:
                np.testing.assert_array_equal(f.result(timeout=30),
                                              ps.serve(x[i : i + 1]))
    s = srv.stats.summary()
    assert s["failed"] == 1 and s["completed"] == 7
    assert srv.retraces_after_warmup == 0
    srv.stats.assert_accounting()


class _Kill:
    """Kills the dispatcher once, with batch A launched and still on the
    device: at the tick that brings request C, once batch B is launched
    too (``'tick'``), or inside batch B's launch (``'launch'``)."""

    def __init__(self, where):
        self.where = where
        self.launches = 0
        self.fired = False
        self.restarts = 0

    def on_tick(self, n):
        if self.where == "tick" and self.launches == 2 and not self.fired:
            self.fired = True
            raise KeyboardInterrupt("dispatcher killed between launches")

    def on_restart(self, restarts):
        self.restarts = restarts

    def pre_bucket(self, b):
        pass

    def pre_dispatch(self, pendings):
        pass

    def pre_serve(self, pendings, xb):
        self.launches += 1
        if self.where == "launch" and self.launches == 2 and not self.fired:
            self.fired = True
            time.sleep(0.1)  # request C is admitted meanwhile
            raise KeyboardInterrupt("dispatcher killed mid-launch")
        return xb

    def post_serve(self, pendings, y):
        return y


@pytest.mark.parametrize("where", ["tick", "launch"])
def test_crash_with_two_batches_in_flight(served, where):
    """A dispatcher crash with batch A on the device and batch B launched
    (or being launched): the batch the dispatcher was launching fails
    typed ServerCrashed, launched batches are fetched and settled by the
    completion thread, and neither is requeued — only the undispatched
    request C rides the supervised restart. The books balance."""
    _, _, x, ps = served
    dev = _Device(0.3)
    inj = _Kill(where)
    srv = CNNServer(on_device(ps, dev), max_wait_ms=50.0, faults=inj)
    sup = Supervisor(srv, backoff_s=0.01, backoff_max_s=0.05)
    with sup:
        sup.warmup()
        futs = [sup.submit(x[i : i + 1]) for i in range(8)]  # batches A, B
        _wait_for(lambda: inj.launches == 2, "batch B never launched")
        f_c = sup.submit(x[8:9])
        a, b = futs[:4], futs[4:]
        for i, f in enumerate(a):
            np.testing.assert_array_equal(f.result(timeout=30),
                                          ps.serve(x[i : i + 1]))
        for i, f in enumerate(b, start=4):
            if where == "launch":
                with pytest.raises(ServerCrashed):
                    f.result(timeout=30)
            else:
                np.testing.assert_array_equal(f.result(timeout=30),
                                              ps.serve(x[i : i + 1]))
        np.testing.assert_array_equal(f_c.result(timeout=30),
                                      ps.serve(x[8:9]))
    s = sup.stats
    assert inj.fired and s.restarts == 1 and inj.restarts == 1
    assert s.requeued == 1                       # C alone: A and B never
    assert s.failed == (4 if where == "launch" else 0)
    assert s.completed == 9 - s.failed
    s.assert_accounting()


# ------------------------------------------------------------- reload
def test_reload_lands_between_dispatches(served, tmp_path):
    """A hot reload under closed-loop traffic with batches overlapped:
    the dispatcher launches every batch on the old set until the swap and
    on the new one after it, every launched batch is fetched and settled,
    and every answer is bit-identical (the same weights)."""
    model, qparams, x, ps = served
    ckpt_save(tmp_path, 1, qparams)
    dev = _Device(0.01)
    srv = CNNServer(on_device(ps, dev, "old"), max_wait_ms=1.0)
    sup = Supervisor(
        srv, template=qparams,
        rebuild=lambda tree: on_device(
            model.plan_set(tree, max_batch=4), dev, "new"))
    reloaded = threading.Event()

    def reload():
        time.sleep(0.15)
        sup.reload(tmp_path)
        reloaded.set()

    with sup:
        sup.warmup()
        t = threading.Thread(target=reload)
        t.start()
        pending, done, i, after = [], [], 0, None
        while after is None or len(done) < after + 20:  # 20 after the swap
            assert i < 20000, "reload never landed"
            if after is None and reloaded.is_set():
                after = len(done)
            while len(pending) < 2:  # two outstanding full-bucket requests
                k = i % 9
                pending.append((k, sup.submit(x[k : k + 4])))
                i += 1
            k, f = pending.pop(0)
            done.append((k, f.result(timeout=30)))
        t.join()
        for k, f in pending:
            done.append((k, f.result(timeout=30)))
    tags = [e[1] for e in dev.events("launch", DISPATCH)]
    first_new = tags.index("new")
    assert first_new > 0 and set(tags[first_new:]) == {"new"}
    assert len(dev.events("fetch", "cnn-serve-complete")) == len(tags)
    for k, y in done:
        np.testing.assert_array_equal(y, ps.serve(x[k : k + 4]))
    assert sup.stats.reloads == 1 and sup.retraces_after_warmup == 0
    assert sup.stats.overlapped_batches > 0
    sup.stats.assert_accounting()


def test_stress_many_clients_books_balance(served):
    """Sixteen client threads, more than the cores, with the interpreter
    switching threads every 10µs: every answer is its request's own, the
    counters the dispatcher and the completion thread both write lose no
    update, and the books balance."""
    _, _, x, ps = served
    dev = _Device(0.002)
    srv = CNNServer(on_device(ps, dev), max_wait_ms=1.0)
    results, errors = [], []
    lock = threading.Lock()

    def client(c):
        try:
            for j in range(6):
                k = (c + j) % 10
                n = 1 + (c + j) % 3
                y = srv.submit(x[k : k + n]).result(timeout=60)
                with lock:
                    results.append((k, n, y))
        except Exception as e:  # noqa: BLE001 — reported below
            with lock:
                errors.append(e)

    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        with srv:
            srv.warmup()
            threads = [threading.Thread(target=client, args=(c,)) for c in range(16)]
            for t in threads:
                t.start()
            for t in threads:
                t.join(timeout=120)
            assert not any(t.is_alive() for t in threads)
    finally:
        sys.setswitchinterval(old)
    assert not errors and len(results) == 16 * 6
    for k, n, y in results:
        np.testing.assert_array_equal(y, ps.serve(x[k : k + n]))
    s = srv.stats
    assert s.completed == s.submitted == sum(n for _, n, _ in results)
    assert s.batches == sum(s.bucket_counts.values())
    assert s.served_samples == sum(b * c for b, c in s.bucket_counts.items())
    assert s.batches == len(dev.events("launch", DISPATCH))
    assert 0 < s.overlapped_batches < s.batches
    assert s.dispatched_requests == len(results)
    s.assert_accounting()
