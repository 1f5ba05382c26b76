"""Self-healing serving lifecycle tests (DESIGN.md §15).

Unit tests drive the ``Supervisor``'s backoff/breaker arithmetic with an
injected clock and seeded RNG (no threads, no sleeps — the §14
``MicroBatcher`` style), and integration tests run real supervised
restarts, hot reloads, and bucket demotion on the ref-kernel smoke CNN
through the deterministic ``FaultInjector`` seams — never by
monkeypatching server internals.
"""
import dataclasses
import threading
import time

import jax
import numpy as np
import pytest

from repro.checkpoint.store import CorruptCheckpointError, save as ckpt_save
from repro.configs import smoke_cnn_config
from repro.launch.faults import FaultInjector, corrupt_checkpoint
from repro.launch.server import CNNServer, ServerCrashed
from repro.launch.supervisor import Supervisor
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


def _supervised(plan_set, *, inj=None, **sup_kw):
    srv = CNNServer(plan_set, max_wait_ms=2.0, faults=inj)
    sup_kw.setdefault("backoff_s", 0.01)
    sup_kw.setdefault("backoff_max_s", 0.05)
    return Supervisor(srv, **sup_kw)


def _submit_retrying(sup, x, *, tries=2000):
    """Offer a request again through a restart gap, never dropping it."""
    for _ in range(tries):
        try:
            return sup.submit(x)
        except (ServerCrashed, RuntimeError):
            time.sleep(0.002)
    raise AssertionError("restart gap never closed")


# ------------------------------------------------- backoff/breaker units
def test_backoff_bounded_exponential_with_jitter(served):
    _, _, _, ps = served
    sup = Supervisor(CNNServer(ps), backoff_s=0.05, backoff_max_s=2.0,
                     jitter=0.25, seed=3)
    delays = [sup._next_backoff(n) for n in range(1, 12)]
    for n, d in enumerate(delays, start=1):
        base = min(2.0, 0.05 * 2 ** (n - 1))
        assert base <= d <= base * 1.25, (n, d)  # jittered, never shrunk
    assert max(delays) <= 2.0 * 1.25             # bounded at the cap
    # deterministic: the same seed replays the same jitter sequence
    sup2 = Supervisor(CNNServer(ps), backoff_s=0.05, backoff_max_s=2.0,
                      jitter=0.25, seed=3)
    assert delays == [sup2._next_backoff(n) for n in range(1, 12)]


def test_breaker_counts_only_crashes_inside_window(served):
    _, _, _, ps = served
    sup = Supervisor(CNNServer(ps), max_restarts=2, window_s=10.0)
    for t in (0.0, 1.0):
        sup._crash_times.append(t)
        assert not sup._breaker_open(t)  # 1st, 2nd crash: restart
    sup._crash_times.append(2.0)
    assert sup._breaker_open(2.0)        # 3rd inside the window: open
    # crashes older than the window no longer count against the budget
    sup2 = Supervisor(CNNServer(ps), max_restarts=2, window_s=10.0)
    for t in (0.0, 1.0, 100.0):
        sup2._crash_times.append(t)
    assert not sup2._breaker_open(100.0)
    assert sup2._crash_times == [100.0]  # pruned to the window


def test_supervisor_validates_config(served):
    _, _, _, ps = served
    with pytest.raises(ValueError, match="max_restarts"):
        Supervisor(CNNServer(ps), max_restarts=0)
    with pytest.raises(ValueError, match="backoff"):
        Supervisor(CNNServer(ps), backoff_s=1.0, backoff_max_s=0.5)


# --------------------------------------------------- supervised restart
def test_restart_requeues_and_books_span_the_crash(served):
    """One transient dispatcher kill: the supervisor restarts, requeues
    the admitted-but-undispatched requests, every future resolves
    bit-identical, and a single ServerStats balances the accounting
    identity across the whole supervised run."""
    _, _, x, ps = served
    inj = FaultInjector(kill_after_dispatches=1, kills=1)
    sup = _supervised(ps, inj=inj)
    ref = [np.asarray(ps.plans[1].serve(x[i : i + 1])) for i in range(10)]
    with sup:
        sup.warmup()
        futs = []
        for i in range(10):
            futs.append(_submit_retrying(sup, x[i : i + 1]))
            time.sleep(0.004)  # spaced past max_wait: several dispatcher
            # ticks run, so the kill seam fires with work queued behind it
        timeout = sup.request_timeout_s()
        for i, f in enumerate(futs):
            np.testing.assert_array_equal(
                np.asarray(f.result(timeout=timeout)), ref[i])
        sup.stats.assert_accounting()
        assert sup.health()["status"] == "ready"
    assert sup.stats.restarts == 1
    assert sup.stats.requeued >= 1        # the kill fires with queued work
    assert inj.restarts == 1              # recovery went through supervision
    assert sup.retraces_after_warmup == 0  # plans stayed compiled


def test_crash_loop_opens_breaker_and_fails_typed(served):
    """An unbounded kill loop: after max_restarts crashes inside the
    window the breaker opens — health() is 'failed' with a reason, the
    stranded requests fail typed ServerCrashed, and the books balance."""
    _, _, x, ps = served
    inj = FaultInjector(kill_after_dispatches=0)  # every tick kills
    sup = _supervised(ps, inj=inj, max_restarts=2, backoff_s=0.005,
                      backoff_max_s=0.01)
    with sup:
        fut = _submit_retrying(sup, x[:1])
        deadline = time.monotonic() + 10
        while sup.health()["status"] != "failed":
            assert time.monotonic() < deadline, "breaker never opened"
            try:
                sup.submit(x[:1])
            except Exception:
                pass
            time.sleep(0.002)
        h = sup.health()
        assert h["status"] == "failed" and "crash loop" in h["reason"]
        assert sup.stats.restarts == 2    # restarted twice, then held down
        with pytest.raises(ServerCrashed):
            fut.result(timeout=5)
        sup.stats.assert_accounting()


def test_stop_during_backoff_interrupts_and_cancels(served):
    """stop() landing mid-backoff returns immediately (no sleep-out of
    the delay) and the crash-stranded futures get CancelledError."""
    _, _, x, ps = served
    inj = FaultInjector(kill_after_dispatches=0, kills=1)
    sup = _supervised(ps, inj=inj, backoff_s=30.0, backoff_max_s=30.0)
    sup.start()
    fut = sup.submit(x[:1])
    deadline = time.monotonic() + 5
    while sup.health()["status"] != "restarting":
        assert time.monotonic() < deadline, "kill never delivered"
        time.sleep(0.002)
    t0 = time.monotonic()
    sup.stop()
    assert time.monotonic() - t0 < 5.0    # did not sleep out the 30s backoff
    with pytest.raises(Exception) as ei:
        fut.result(timeout=1)
    assert "Cancelled" in type(ei.value).__name__
    sup.stats.assert_accounting()


def test_stop_is_idempotent(served):
    _, _, x, ps = served
    sup = _supervised(ps)
    with sup:
        f = sup.submit(x[:1])
        f.result(timeout=30)
    sup.stop()   # second stop after the context exit: no-op, no raise
    sup.stop()
    sup.stats.assert_accounting()


def test_at_most_once_inflight_fails_typed_undispatched_requeues(served):
    """The §15 at-most-once split: a request *inside a dispatch* when the
    dispatcher dies fails typed ServerCrashed (never re-executed), while
    an admitted-but-undispatched request rides the requeue and completes
    after the restart."""
    _, _, x, ps = served

    class _MidDispatchKill:
        """Duck-typed injector: the first pre_serve (inside _run, with
        the batch already marked in-flight) holds the dispatcher long
        enough for a second request to queue behind it, then dies with a
        BaseException — which skips the Exception-level bisect isolation
        and crashes the loop itself."""

        def __init__(self):
            self.armed = True
            self.restarts = 0

        def on_tick(self, n):
            pass

        def on_restart(self, restarts):
            self.restarts = restarts

        def pre_bucket(self, b):
            pass

        def pre_dispatch(self, pendings):
            pass

        def pre_serve(self, pendings, xb):
            if self.armed:
                self.armed = False
                time.sleep(0.08)  # let the co-test request get admitted
                raise KeyboardInterrupt("dispatcher died mid-dispatch")
            return xb

        def post_serve(self, pendings, y):
            return y

    inj = _MidDispatchKill()
    sup = _supervised(ps, inj=inj)
    ref = np.asarray(ps.plans[1].serve(x[1:2]))
    with sup:
        sup.warmup()
        f_inflight = sup.submit(x[:1])
        time.sleep(0.03)              # f_inflight is inside the dispatch…
        f_queued = sup.submit(x[1:2])  # …while this one is still queued
        with pytest.raises(ServerCrashed):
            f_inflight.result(timeout=30)
        np.testing.assert_array_equal(
            np.asarray(f_queued.result(timeout=30)), ref)
        sup.stats.assert_accounting()
    assert sup.stats.restarts == 1 and inj.restarts == 1
    assert sup.stats.requeued == 1    # exactly the undispatched request
    assert sup.stats.failed >= 1      # exactly the in-flight one, typed


def test_requeue_rejects_crashed_unreaped_server(served):
    """requeue() into a crashed-but-unreaped server is a bug (the dead
    dispatcher would never drain it) — typed RuntimeError; after stop()
    reaps the thread the pre-start requeue path is allowed."""
    _, _, x, ps = served
    inj = FaultInjector(kill_after_dispatches=0, kills=1)
    srv = CNNServer(ps, max_wait_ms=2.0, faults=inj)
    stranded = []
    srv.on_crash = lambda exc, pend: stranded.extend(pend)
    with srv:
        srv.submit(x[:1])
        deadline = time.monotonic() + 5
        while not stranded:
            assert time.monotonic() < deadline, "kill never delivered"
            time.sleep(0.002)
        with pytest.raises(RuntimeError, match="reap"):
            srv.requeue(stranded)
        srv.stop(drain=False)             # reap the dead dispatcher
        assert srv.requeue(stranded) == 1  # pre-start requeue allowed
        srv.start(fresh_stats=False)
        np.testing.assert_array_equal(
            np.asarray(stranded[0].future.result(timeout=30)),
            np.asarray(ps.plans[1].serve(x[:1])))
    srv.stats.assert_accounting()


# ------------------------------------------------------------ hot reload
def test_hot_reload_swaps_atomically_and_corrupt_leaves_old(served, tmp_path):
    """reload(): a verified checkpoint swaps the plan set mid-traffic
    with zero retraces; a corrupted latest checkpoint fails typed with
    the old plan still serving bit-identical; fallback=True walks back
    to the newest verifiable step."""
    model, qparams, x, ps = served
    ckpt_save(tmp_path, 1, qparams)
    ckpt_save(tmp_path, 2, qparams)
    srv = CNNServer(ps, max_wait_ms=2.0)
    sup = Supervisor(
        srv,
        rebuild=lambda tree: model.plan_set(tree, max_batch=4),
        template=qparams,
    )
    with sup:
        sup.warmup()
        y0 = np.asarray(sup.submit(x[:1]).result(timeout=30))
        step, fp = sup.reload(tmp_path)
        assert step == 2 and fp == ps.fingerprint
        np.testing.assert_array_equal(
            np.asarray(sup.submit(x[:1]).result(timeout=30)), y0)
        assert sup.retraces_after_warmup == 0  # warmed before the swap
        corrupt_checkpoint(tmp_path, step=2, mode="flip")
        with pytest.raises(CorruptCheckpointError):
            sup.reload(tmp_path)
        assert sup.reload_failures == 1
        np.testing.assert_array_equal(  # old plan kept serving
            np.asarray(sup.submit(x[:1]).result(timeout=30)), y0)
        step3, _ = sup.reload(tmp_path, fallback=True)
        assert step3 == 1 and sup.stats.reloads == 2
        sup.stats.assert_accounting()


def test_reload_requires_rebuild_and_template(served, tmp_path):
    _, _, _, ps = served
    sup = Supervisor(CNNServer(ps))
    with pytest.raises(RuntimeError, match="rebuild"):
        sup.reload(tmp_path)


def test_swap_plan_set_validates_ladder(served):
    """The atomic swap refuses a plan set whose bucket ladder differs —
    the micro-batcher's aggregation targets would dangle."""
    model, qparams, _, ps = served
    other = model.plan_set(qparams, max_batch=2)
    srv = CNNServer(ps, max_wait_ms=2.0)
    with srv:
        with pytest.raises(ValueError, match="ladder"):
            srv.swap_plan_set(other)


# ------------------------------------------------- kernel-fallback demote
def test_demote_after_strikes_probe_repromotes(served):
    """Per-bucket degradation: demote_after consecutive compiled-path
    faults demote exactly that bucket to its bit-compatible fallback
    (health 'degraded' with the reason), a transient single fault does
    NOT demote, and after the backend heals the probe_every-th dispatch
    re-promotes."""
    model, qparams, x, ps = served
    fallback = model.fallback_plan_set(qparams, ps)
    inj = FaultInjector()
    srv = CNNServer(ps, max_wait_ms=2.0, faults=inj, fallback=fallback,
                    demote_after=2, probe_every=2)
    ref3 = np.asarray(ps.serve(x[:3]))

    def roundtrip():
        return np.asarray(srv.submit(x[:3]).result(timeout=30))

    with srv:
        srv.warmup()
        inj.fail_bucket(4)
        with pytest.raises(Exception):  # strike 1: below the threshold —
            roundtrip()                 # bubbles to isolation, fails typed
        np.testing.assert_array_equal(roundtrip(), ref3)  # strike 2: demoted
        assert list(srv.demoted_buckets()) == [4]
        h = srv.health()
        assert h["status"] == "degraded" and 4 in h["demoted"]
        assert "bucket-4" in srv.demoted_buckets()[4]
        assert srv.stats.demotions == 1
        # innocent bucket keeps its compiled plan, bit-identical
        np.testing.assert_array_equal(
            np.asarray(srv.submit(x[:1]).result(timeout=30)),
            np.asarray(ps.plans[1].serve(x[:1])))
        # heal: the next probe (every 2nd demoted dispatch) re-promotes
        inj.heal_bucket(4)
        for _ in range(4):
            np.testing.assert_array_equal(roundtrip(), ref3)
            if not srv.demoted_buckets():
                break
        assert not srv.demoted_buckets()
        assert srv.stats.promotions == 1
        assert srv.health()["status"] == "ready"
        srv.stats.assert_accounting()
    assert inj.bucket_faults_fired >= 2


def test_fallback_closures_pin_fingerprint(served):
    """Degradation closures are pinned to the serving weights: building
    them against a differently-quantized model raises StalePlanError
    (serving different numbers under 'degraded' is corruption, not
    degradation)."""
    from repro.models.plan import StalePlanError, fallback_closures

    model, qparams, x, ps = served
    # same structure, different content: perturb one float leaf so the
    # params fingerprint no longer matches the serving plan set's
    flat, treedef = jax.tree_util.tree_flatten(qparams)
    for i, leaf in enumerate(flat):
        if hasattr(leaf, "dtype") and leaf.dtype == np.float32 and leaf.size:
            flat[i] = leaf + 1.0
            break
    other_q = jax.tree_util.tree_unflatten(treedef, flat)
    ref_model = SparseCNN(dataclasses.replace(model.cfg, kernel_mode="ref"))
    other_set = ref_model.plan_set(other_q, buckets=ps.buckets)
    with pytest.raises(StalePlanError):
        fallback_closures(ps, other_set)
