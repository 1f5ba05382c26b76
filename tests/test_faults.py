"""Robustness layer of the serving tier (DESIGN.md §14).

Chaos-style tests through the deterministic fault injector
(`repro.launch.faults`) installed at the server's hook seams — no
monkeypatching of internals. Covers admission validation (every
`bad_input` kind rejected alone), blast-radius isolation (poison in a
full co-batch: innocents bit-identical, exactly the poison typed-failed,
zero bisect retraces), overload shedding (reject with measured
retry-after / block backpressure), deadline expiry before dispatch,
dispatcher-crash supervision (`ServerCrashed`, clean restart), health
reporting, and the `completed+rejected+failed+expired == offered`
accounting identity on every path.
"""
import dataclasses
import time
from concurrent.futures import CancelledError

import jax
import numpy as np
import pytest

from repro.configs import smoke_cnn_config
from repro.launch.faults import FaultInjected, FaultInjector, bad_input
from repro.launch.server import CNNServer, DeadlineExceeded, InvalidRequest, \
    NumericalFault, Overloaded, ServerCrashed, validate_request
from repro.models.cnn import SparseCNN


@pytest.fixture(scope="module")
def served():
    """Ref-kernel quantized model + a max_batch=4 bucketed plan set."""
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


# ------------------------------------------------------------ admission
def test_sample_spec_plumbed_from_config(served):
    _, _, x, ps = served
    assert ps.sample_spec == (tuple(x.shape[1:]), "float32")


@pytest.mark.parametrize("kind", ["shape", "rank", "dtype", "nan", "inf"])
def test_validate_request_rejects_bad_inputs(served, kind):
    _, _, x, ps = served
    with pytest.raises(InvalidRequest):
        validate_request(bad_input(kind, x.shape[1:]), ps.sample_spec)
    validate_request(x[:1], ps.sample_spec)  # a good request passes


@pytest.mark.parametrize("kind", ["shape", "dtype", "nan"])
def test_submit_rejects_bad_input_alone(served, kind):
    """A malformed request is rejected at admission — counted, typed,
    and without touching the innocent request served beside it."""
    _, _, x, ps = served
    srv = CNNServer(ps, max_wait_ms=20.0)
    with srv:
        srv.warmup()
        with pytest.raises(InvalidRequest):
            srv.submit(bad_input(kind, x.shape[1:]))
        good = srv.submit(x[:1]).result(timeout=30)
    np.testing.assert_array_equal(good, np.asarray(ps.serve(x[:1])))
    s = srv.stats.summary()
    assert s["rejected"] == 1 and s["completed"] == 1 and s["offered"] == 2
    srv.stats.assert_accounting()
    assert srv.retraces_after_warmup == 0


def test_submit_rejects_nonpositive_deadline(served):
    _, _, x, ps = served
    with CNNServer(ps) as srv:
        with pytest.raises(InvalidRequest):
            srv.submit(x[:1], deadline_s=0.0)
    srv.stats.assert_accounting()


# ------------------------------------------------- blast-radius isolation
def _co_batch(srv, inj_or_none, reqs, max_wait_ms):
    """Submit reqs[0] as a plug, let it dispatch alone, then submit the
    rest quickly so they co-batch behind the (slow) plug."""
    futures = [srv.submit(reqs[0])]
    time.sleep(3 * max_wait_ms / 1e3)
    futures += [srv.submit(r) for r in reqs[1:]]
    return futures


def test_bisect_isolates_raise_poison(served):
    """One raise-poison in a full co-batch: every innocent completes
    bit-identical to a fault-free per-request serve, exactly the poison
    future carries FaultInjected, and bisection (halves pad to warmed
    buckets) adds zero retraces."""
    _, _, x, ps = served
    inj = FaultInjector(slow_s=0.08)
    reqs = [x[i : i + 1] for i in range(5)]  # plug + a full 4-batch
    inj.poison(reqs[2], "raise")
    ref = {i: np.asarray(ps.plans[1].serve(r))
           for i, r in enumerate(reqs) if i != 2}
    srv = CNNServer(ps, max_wait_ms=5.0, faults=inj)
    with srv:
        srv.warmup()
        futures = _co_batch(srv, inj, reqs, 5.0)
        for i, f in enumerate(futures):
            if i == 2:
                with pytest.raises(FaultInjected):
                    f.result(timeout=30)
            else:
                np.testing.assert_array_equal(f.result(timeout=30), ref[i])
    assert srv.retraces_after_warmup == 0
    srv.stats.assert_accounting()
    s = srv.stats.summary()
    assert s["completed"] == 4 and s["failed"] == 1


def test_nan_poison_fails_only_its_request(served):
    """NaN activations (injected past the datapath — NaN *inputs* are
    already rejected at admission) fail exactly the poisoned request
    with NumericalFault; its co-batch is untouched."""
    _, _, x, ps = served
    inj = FaultInjector(slow_s=0.08)
    reqs = [x[i : i + 1] for i in range(5)]
    inj.poison(reqs[3], "nan")
    srv = CNNServer(ps, max_wait_ms=5.0, faults=inj)
    with srv:
        srv.warmup()
        futures = _co_batch(srv, inj, reqs, 5.0)
        for i, f in enumerate(futures):
            if i == 3:
                with pytest.raises(NumericalFault):
                    f.result(timeout=30)
            else:
                np.testing.assert_array_equal(
                    f.result(timeout=30), np.asarray(ps.plans[1].serve(reqs[i]))
                )
    assert srv.retraces_after_warmup == 0
    srv.stats.assert_accounting()


# ------------------------------------------------------------- overload
def test_overload_reject_sheds_with_retry_after(served):
    _, _, x, ps = served
    inj = FaultInjector(slow_s=0.15)          # hold the dispatcher busy
    srv = CNNServer(ps, max_wait_ms=1.0, max_queue=2, shed="reject",
                    faults=inj)
    with srv:
        srv.warmup()
        f1 = srv.submit(x[:1])                # in system: depth 1
        time.sleep(0.02)                      # f1 dispatched (slowly)
        f2 = srv.submit(x[1:2])               # depth 2 == max_queue
        with pytest.raises(Overloaded) as ei:
            srv.submit(x[:1])                 # over the bound: shed
        assert ei.value.retry_after_s > 0
        assert srv.health()["status"] == "degraded"  # at capacity
        f1.result(timeout=30)
        f2.result(timeout=30)
    s = srv.stats.summary()
    assert s["rejected"] == 1 and s["shed_rate"] > 0
    srv.stats.assert_accounting()


def test_overload_block_backpressures(served):
    """shed='block': the submitter waits for space instead of a raise,
    and is admitted once the in-flight request completes."""
    _, _, x, ps = served
    inj = FaultInjector(slow_s=0.1)
    srv = CNNServer(ps, max_wait_ms=1.0, max_queue=1, shed="block",
                    faults=inj)
    with srv:
        srv.warmup()
        f1 = srv.submit(x[:1])
        time.sleep(0.02)
        t0 = time.monotonic()
        f2 = srv.submit(x[1:2])               # blocks until f1 resolves
        blocked = time.monotonic() - t0
        f1.result(timeout=30)
        f2.result(timeout=30)
    assert blocked > 0.02                     # it actually waited
    assert srv.stats.summary()["rejected"] == 0
    srv.stats.assert_accounting()


# ------------------------------------------------------------- deadlines
def test_deadline_expires_before_dispatch(served):
    """A request whose deadline passes while the dispatcher is held busy
    fails with DeadlineExceeded without wasting a bucket dispatch."""
    _, _, x, ps = served
    inj = FaultInjector(slow_s=0.2)
    srv = CNNServer(ps, max_wait_ms=1.0, faults=inj)
    with srv:
        srv.warmup()
        plug = srv.submit(x[:1])
        time.sleep(0.02)                      # plug dispatched, 0.2s serve
        doomed = srv.submit(x[1:2], deadline_s=0.05)
        dispatches_before = inj.dispatches
        with pytest.raises(DeadlineExceeded):
            doomed.result(timeout=30)
        plug.result(timeout=30)
    # the expired request never reached pre_serve: only the plug dispatched
    assert inj.dispatches == dispatches_before
    s = srv.stats.summary()
    assert s["expired"] == 1 and s["completed"] == 1
    srv.stats.assert_accounting()


def test_deadline_met_flushes_early(served):
    """With a huge max_wait, a deadline request still completes: the
    batcher tightens the flush time to the deadline less the server's
    flush lead (measured service time plus the dispatcher's own wake-up
    slack), so the batch is dispatched before the deadline, not at the
    10s max-wait."""
    _, _, x, ps = served
    srv = CNNServer(ps, max_wait_ms=10_000.0)
    with srv:
        srv.warmup()
        out = srv.submit(x[:1], deadline_s=1.0).result(timeout=30)
    np.testing.assert_array_equal(out, np.asarray(ps.serve(x[:1])))
    s = srv.stats
    assert s.dispatched_requests == 1 and s.expired == 0
    # held for co-batchers until the deadline flush (its lead is a few ms
    # here), and dispatched before the deadline
    assert 0.5 <= s.queue_wait_s < 1.0
    s.assert_accounting()


# ----------------------------------------------------------- supervision
def test_dispatcher_crash_fails_pending_and_restart_recovers(served):
    _, _, x, ps = served
    inj = FaultInjector(kill_after_dispatches=0)  # first tick with work dies
    srv = CNNServer(ps, max_wait_ms=5.0, faults=inj)
    srv.start()
    srv.warmup()
    fut = srv.submit(x[:1])
    with pytest.raises(ServerCrashed):
        fut.result(timeout=30)
    with pytest.raises(ServerCrashed):
        srv.submit(x[:1])                     # submit is poisoned too
    h = srv.health()
    assert h["status"] == "stopped" and h["crashed"]
    assert srv.stats.summary()["failed"] == 1
    srv.stats.assert_accounting()
    srv.stop()

    inj.kill_after_dispatches = None          # operator fixed the fault
    srv.start()                               # restart: fresh books
    assert srv.stats.summary()["offered"] == 0
    assert srv.health()["status"] == "ready"
    out = srv.submit(x[:1]).result(timeout=30)
    np.testing.assert_array_equal(out, np.asarray(ps.serve(x[:1])))
    assert srv.retraces_after_warmup == 0     # buckets stayed compiled
    srv.stop()
    srv.stats.assert_accounting()


def test_health_degrades_on_fault_and_recovers(served):
    _, _, x, ps = served
    inj = FaultInjector()
    poison = inj.poison(np.array(x[5:6]))     # lone poison: no co-batch
    srv = CNNServer(ps, max_wait_ms=5.0, faults=inj)
    with srv:
        srv.warmup()
        assert srv.health()["status"] == "ready"
        with pytest.raises(FaultInjected):
            srv.submit(poison).result(timeout=30)
        assert srv.health()["status"] == "degraded"
        srv.submit(x[:1]).result(timeout=30)  # a clean batch clears it
        assert srv.health()["status"] == "ready"
    assert srv.health()["status"] == "stopped"
    srv.stats.assert_accounting()


def test_stop_timeout_abandons_drain(served):
    """stop(timeout_s=) bounds the drain: past it, the remaining queue is
    cancelled (CancelledError for waiters — never a hang) and the books
    still balance."""
    _, _, x, ps = served
    inj = FaultInjector(slow_s=0.4)           # each dispatch outlives the
    srv = CNNServer(ps, max_wait_ms=1.0, faults=inj)  # 0.2s drain budget
    srv.start()
    srv.warmup()
    futures = [srv.submit(x[i : i + 1]) for i in range(8)]
    t0 = time.monotonic()
    srv.stop(timeout_s=0.2)
    # one in-flight 0.4s dispatch finishes; everything after is cancelled
    assert time.monotonic() - t0 < 2.0        # nowhere near 8 x 0.4s
    outcomes = {"done": 0, "cancelled": 0}
    for f in futures:
        try:
            f.result(timeout=1)
            outcomes["done"] += 1
        except CancelledError:
            outcomes["cancelled"] += 1
    assert outcomes["cancelled"] > 0 and outcomes["done"] > 0
    assert sum(outcomes.values()) == 8
    srv.stats.assert_accounting()


# ------------------------------------------------ the serve CLI exit code
@pytest.mark.parametrize("poison", [False, True], ids=["clean", "poisoned"])
def test_serve_cli_exit_code(monkeypatch, tmp_path, poison):
    """`python -m repro.launch.serve --server` exits 1 when a request
    fails (here: one poisoned request, injected at the server's fault
    seam), and returns normally (exit 0) when every request is answered."""
    import functools

    from repro.launch import serve, server

    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    inj = FaultInjector()
    if poison:  # serve.py's request pool: PRNGKey(1) normals, --batch rows
        cfg = smoke_cnn_config("sparse-cnn-tiny")
        pool = np.asarray(jax.random.normal(
            jax.random.PRNGKey(1),
            (4, cfg.image_size, cfg.image_size, cfg.in_channels)))
        inj.poison(pool[0][None])
    monkeypatch.setattr(server, "CNNServer",
                        functools.partial(server.CNNServer, faults=inj))
    argv = ["--arch", "sparse-cnn-tiny", "--smoke", "--server", "--batch", "4",
            "--max-batch", "4", "--requests", "8", "--rate", "400"]
    if poison:
        with pytest.raises(SystemExit) as e:
            serve.main(argv)
        assert e.value.code == 1
    else:
        results = serve.main(argv)
        assert len(results) == 8 and all(r is not None for r in results)
