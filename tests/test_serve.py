"""Continuous-batching serving tier (DESIGN.md §11).

Covers the four legs of the tier: bucket selection (`make_buckets` /
`PlanSet.bucket_for`), ragged-tail pad/slice bit-exactness vs per-request
`plan.serve`, queue aggregation under max-batch/max-wait (pure
`MicroBatcher` logic with an injectable clock + the threaded `CNNServer`
end to end), and data-parallel mesh serving on a 2x2 `make_test_mesh`
matching single-device logits bit for bit (subprocess, like
test_distributed, so the fake-device override never leaks).
"""
import dataclasses
import json
import os
import pathlib
import subprocess
import sys
import textwrap
import time
from concurrent.futures import CancelledError, Future

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import smoke_cnn_config
from repro.launch.server import CNNServer, MicroBatcher, _Pending, auto_rate, \
    burst_arrivals, poisson_arrivals
from repro.models.cnn import SparseCNN
from repro.models.plan import PlanSet, StalePlanError, make_buckets

REPO = pathlib.Path(__file__).resolve().parents[1]


# ------------------------------------------------------------- fixtures
def _quantized_model(kernel_mode: str):
    cfg = dataclasses.replace(
        smoke_cnn_config("sparse-cnn-tiny", sparsity=0.625),
        kernel_mode=kernel_mode,
    )
    model = SparseCNN(cfg)
    params = model.compress(model.init(jax.random.PRNGKey(0)))
    x = jax.random.normal(
        jax.random.PRNGKey(1),
        (12, cfg.image_size, cfg.image_size, cfg.in_channels),
    )
    _, stats = model.apply(params, x[:4], collect_act_stats=True)
    return model, model.quantize(params, stats), x


@pytest.fixture(scope="module")
def ref_served():
    """Ref-kernel model + quantized params + a bucketed plan set."""
    model, qparams, x = _quantized_model("ref")
    plan_set = model.plan_set(qparams, max_batch=8)
    return model, qparams, x, plan_set


@pytest.fixture(scope="module")
def pallas_served():
    model, qparams, x = _quantized_model("pallas")
    plan_set = model.plan_set(qparams, max_batch=4)
    return model, qparams, x, plan_set


# ------------------------------------------------------ bucket selection
def test_make_buckets_ladder():
    assert make_buckets(8) == (1, 2, 4, 8)
    assert make_buckets(1) == (1,)
    assert make_buckets(5) == (1, 2, 4, 8)  # first bucket >= max_batch
    assert make_buckets(6, dp=2) == (2, 4, 8)
    assert make_buckets(4, dp=4) == (4,)


def test_make_buckets_validates():
    with pytest.raises(ValueError):
        make_buckets(0)
    with pytest.raises(ValueError):
        make_buckets(4, dp=0)


def test_bucket_for(ref_served):
    _, _, _, ps = ref_served
    assert ps.buckets == (1, 2, 4, 8)
    assert ps.bucket_for(1) == 1
    assert ps.bucket_for(3) == 4
    assert ps.bucket_for(8) == 8
    assert ps.bucket_for(9) is None  # serve() chunks at the largest bucket


def test_plan_set_validates(ref_served):
    model, qparams, _, ps = ref_served
    with pytest.raises(ValueError):
        PlanSet(ps.model, ps.fingerprint, (4, 2), dict(ps.plans))
    with pytest.raises(ValueError):
        PlanSet(ps.model, ps.fingerprint, (1, 2), dict(ps.plans))
    with pytest.raises(ValueError):
        model.plan_set(qparams, buckets=(2, 3), dp=2)  # 3 not a dp multiple
    with pytest.raises(ValueError):
        model.plan_set(qparams)  # needs max_batch or buckets


# ------------------------------------- ragged pad/slice bit-exactness
@pytest.mark.parametrize("n", [1, 2, 3, 5, 7, 8, 11])
def test_ragged_serve_matches_per_request(ref_served, n):
    """Padding to the bucket and slicing back == serving each request
    alone (n=11 > the largest bucket also exercises chunking)."""
    _, _, x, ps = ref_served
    got = ps.serve(x[:n])
    per = jnp.concatenate([ps.plans[1].serve(x[i : i + 1]) for i in range(n)])
    np.testing.assert_array_equal(np.asarray(got), np.asarray(per))


def test_ragged_serve_matches_per_request_pallas_int8(pallas_served):
    """Same bit-exactness through the §9 int8-resident Pallas chain."""
    _, _, x, ps = pallas_served
    got = ps.serve(x[:3])
    per = jnp.concatenate([ps.plans[1].serve(x[i : i + 1]) for i in range(3)])
    np.testing.assert_array_equal(np.asarray(got), np.asarray(per))


def test_host_path_matches_device_path(ref_served):
    """numpy input (the serving tier's host-assembly fast path) returns
    numpy and matches the on-device path bit for bit."""
    _, _, x, ps = ref_served
    host = ps.serve(np.asarray(x[:5]))
    assert isinstance(host, np.ndarray)
    np.testing.assert_array_equal(host, np.asarray(ps.serve(x[:5])))


def test_serve_matches_unplanned_apply(ref_served):
    """The whole bucketed path stays bit-identical to plain apply."""
    model, qparams, x, ps = ref_served
    np.testing.assert_array_equal(
        np.asarray(ps.serve(x[:6])), np.asarray(model.apply(qparams, x[:6]))
    )


def test_serve_rejects_empty(ref_served):
    _, _, x, ps = ref_served
    with pytest.raises(ValueError):
        ps.serve(x[:0])


# ----------------------------------------------- zero-retrace contract
def test_no_retrace_after_warmup(ref_served):
    _, _, x, ps = ref_served
    base = ps.warmup(x.shape[1:])
    assert base >= len(ps.buckets)
    for n in (1, 2, 3, 5, 8, 11):          # every ragged size pads to a bucket
        ps.serve(np.asarray(x[:n]))
        ps.serve(x[:n])
    assert ps.trace_count == base


def test_trace_count_counts_new_shapes(ref_served):
    _, _, x, ps = ref_served
    ps.warmup(x.shape[1:])
    before = ps.plans[2].trace_count
    ps.plans[2].serve(x[:2])               # warmed: no new trace
    assert ps.plans[2].trace_count == before
    ps.plans[2].serve(x[:3])               # off-bucket direct use: retrace
    assert ps.plans[2].trace_count == before + 1


def test_plan_set_staleness(ref_served):
    model, qparams, x, ps = ref_served
    ps.check(qparams)                      # matching params pass
    _, stats = model.apply(qparams, x[:2], collect_act_stats=True)
    requant = model.quantize(
        model.compress(model.constrain(model.init(jax.random.PRNGKey(3)))),
        stats,
    )
    with pytest.raises(StalePlanError):
        ps.check(requant)


# --------------------------------------------------- queue aggregation
def _pending(n=1, arrival=0.0, deadline=None):
    return _Pending(x=np.zeros((n, 4)), n=n, arrival=arrival, future=Future(),
                    deadline=deadline)


def test_microbatcher_flushes_at_max_batch():
    mb = MicroBatcher(max_batch=4, max_wait_s=10.0)
    assert mb.add(_pending()) == []
    assert mb.add(_pending()) == []
    assert mb.add(_pending()) == []
    flushed = mb.add(_pending())
    assert len(flushed) == 1 and len(flushed[0]) == 4
    assert len(mb) == 0


def test_microbatcher_max_wait_deadline():
    mb = MicroBatcher(max_batch=8, max_wait_s=0.5)
    assert mb.deadline() is None and not mb.due(99.0)
    mb.add(_pending(arrival=10.0))
    mb.add(_pending(arrival=10.3))
    assert mb.deadline() == pytest.approx(10.5)  # oldest arrival governs
    assert not mb.due(10.4)
    assert mb.due(10.5)
    batch = mb.take()
    assert len(batch) == 2 and mb.deadline() is None


def test_microbatcher_never_splits_requests():
    mb = MicroBatcher(max_batch=4, max_wait_s=10.0)
    mb.add(_pending(n=3))
    flushed = mb.add(_pending(n=2))        # would overflow: prior flushes alone
    assert [len(b) for b in flushed] == [1]
    assert flushed[0][0].n == 3 and len(mb) == 2


def test_microbatcher_oversize_request_is_own_batch():
    mb = MicroBatcher(max_batch=4, max_wait_s=10.0)
    flushed = mb.add(_pending(n=6))        # > max_batch: flushes immediately
    assert [len(b) for b in flushed] == [1] and flushed[0][0].n == 6


def test_microbatcher_validates():
    with pytest.raises(ValueError):
        MicroBatcher(0, 1.0)
    with pytest.raises(ValueError):
        MicroBatcher(4, -1.0)


def test_microbatcher_request_deadline_tightens_flush():
    """A pending request deadline pulls the flush time earlier than the
    max-wait, less the caller's service estimate — so queue wait is
    charged against the request's budget, not ignored."""
    mb = MicroBatcher(max_batch=8, max_wait_s=5.0)
    mb.add(_pending(arrival=10.0))                      # max-wait: 15.0
    mb.add(_pending(arrival=10.1, deadline=12.0))
    assert mb.deadline() == pytest.approx(12.0)         # deadline governs
    assert mb.deadline(service_est_s=0.5) == pytest.approx(11.5)
    assert not mb.due(11.0, service_est_s=0.5)
    assert mb.due(11.5, service_est_s=0.5)
    mb.take()
    assert mb.deadline() is None


def test_microbatcher_expired_deadline_coexists_with_batch_full():
    """An already-expired pending plus a batch-full flush in one add():
    the full flush carries the expired request along (ordering
    preserved), leaving the dispatcher to expire it — the batcher never
    drops or reorders requests."""
    mb = MicroBatcher(max_batch=2, max_wait_s=5.0)
    expired = _pending(arrival=0.0, deadline=1.0)
    mb.add(expired)
    assert mb.due(2.0)                                  # past its deadline
    flushed = mb.add(_pending(arrival=2.0))             # and batch-full now
    assert len(flushed) == 1 and flushed[0][0] is expired
    assert [p.deadline for p in flushed[0]] == [1.0, None]
    assert len(mb) == 0 and not mb.due(99.0)


# ------------------------------------------------- threaded server e2e
def test_server_end_to_end(ref_served):
    """5 single-sample requests, max_batch=4: one full flush + one
    max-wait flush; results bit-identical to direct bucketed serving."""
    _, _, x, ps = ref_served
    pool = np.asarray(x)
    srv = CNNServer(ps, max_batch=4, max_wait_ms=50.0)
    with srv:
        srv.warmup(x.shape[1:])
        futures = [srv.submit(pool[i : i + 1]) for i in range(5)]
        results = [f.result(timeout=30) for f in futures]
    direct = ps.serve(pool[:5])
    np.testing.assert_array_equal(np.concatenate(results), direct)
    assert srv.retraces_after_warmup == 0
    s = srv.stats.summary()
    assert s["completed"] == s["offered"] == 5
    assert s["bucket_counts"] == {"1": 1, "4": 1}
    assert s["p50_us"] > 0 and s["p99_us"] >= s["p50_us"]
    assert s["accounting_ok"] and s["rejected"] == s["failed"] == s["expired"] == 0
    srv.stats.assert_accounting()


def test_server_mixed_request_sizes(ref_served):
    _, _, x, ps = ref_served
    pool = np.asarray(x)
    srv = CNNServer(ps, max_batch=8, max_wait_ms=30.0)
    with srv:
        srv.warmup(x.shape[1:])
        futures = [srv.submit(pool[0:2]), srv.submit(pool[2:3]),
                   srv.submit(pool[3:6])]
        results = [f.result(timeout=30) for f in futures]
    assert [r.shape[0] for r in results] == [2, 1, 3]
    np.testing.assert_array_equal(np.concatenate(results), ps.serve(pool[:6]))
    assert srv.stats.summary()["padded_frac"] > 0  # 6 samples in an 8-bucket
    srv.stats.assert_accounting()


def test_server_max_wait_bounds_latency(ref_served):
    """A lone request must not wait for a full batch: it dispatches
    once max_wait expires."""
    _, _, x, ps = ref_served
    srv = CNNServer(ps, max_batch=8, max_wait_ms=40.0)
    with srv:
        srv.warmup(x.shape[1:])
        t0 = time.monotonic()
        fut = srv.submit(np.asarray(x[:1]))
        fut.result(timeout=30)
        elapsed = time.monotonic() - t0
    assert elapsed >= 0.040 * 0.5           # it did wait (scheduler slack)
    assert srv.stats.summary()["bucket_counts"] == {"1": 1}


def test_server_drains_on_stop(ref_served):
    _, _, x, ps = ref_served
    srv = CNNServer(ps, max_batch=8, max_wait_ms=10_000.0)  # never self-flush
    srv.start()
    srv.warmup(x.shape[1:])
    futures = [srv.submit(np.asarray(x[i : i + 1])) for i in range(3)]
    srv.stop()                              # drain=True serves the remainder
    assert all(f.done() for f in futures)
    np.testing.assert_array_equal(
        np.concatenate([f.result() for f in futures]),
        ps.serve(np.asarray(x[:3])),
    )


def test_server_stop_no_drain_cancels(ref_served):
    """stop(drain=False): queued-but-undispatched futures are cancelled
    (CancelledError for waiters, never a hang), and the accounting
    identity still closes — cancellations count as failed."""
    _, _, x, ps = ref_served
    srv = CNNServer(ps, max_batch=8, max_wait_ms=10_000.0)  # never self-flush
    srv.start()
    srv.warmup(x.shape[1:])
    futures = [srv.submit(np.asarray(x[i : i + 1])) for i in range(3)]
    srv.stop(drain=False)
    for f in futures:
        assert f.cancelled()
        with pytest.raises(CancelledError):
            f.result(timeout=1)
    s = srv.stats.summary()
    assert s["failed"] == 3 and s["completed"] == 0
    srv.stats.assert_accounting()


def test_server_restart_resets_run_state(ref_served):
    """Pins the restart bug: start() after stop() must not reuse the
    previous run's stats or warmup-trace snapshot — the accounting
    identity and the zero-retrace contract are per-run."""
    _, _, x, ps = ref_served
    srv = CNNServer(ps, max_batch=4, max_wait_ms=20.0)
    srv.start()
    srv.warmup(x.shape[1:])
    srv.submit(np.asarray(x[:2])).result(timeout=30)
    srv.stop()
    first = srv.stats.summary()
    assert first["completed"] == first["offered"] == 2

    srv.start()                       # second run: fresh books, no re-warmup
    assert srv.stats.summary()["offered"] == 0
    assert srv.retraces_after_warmup == 0     # re-baselined, buckets warm
    out = srv.submit(np.asarray(x[2:3])).result(timeout=30)
    srv.stop()
    np.testing.assert_array_equal(out, np.asarray(ps.serve(np.asarray(x[2:3]))))
    s = srv.stats.summary()
    assert s["completed"] == s["offered"] == 1  # not 3: stats were reset
    assert srv.retraces_after_warmup == 0
    srv.stats.assert_accounting()


def test_server_rejects_when_not_running(ref_served):
    _, _, x, ps = ref_served
    srv = CNNServer(ps)
    with pytest.raises(RuntimeError):
        srv.submit(np.asarray(x[:1]))
    with pytest.raises(ValueError):
        with srv:
            srv.submit(np.asarray(x[:0]))   # empty batch


# ------------------------------------------------------------ load gen
def test_poisson_arrivals_deterministic_and_rate():
    a = poisson_arrivals(100.0, 500, seed=3)
    b = poisson_arrivals(100.0, 500, seed=3)
    np.testing.assert_array_equal(a, b)
    assert (np.diff(a) > 0).all()
    assert a[-1] == pytest.approx(5.0, rel=0.3)  # ~500 arrivals at 100 rps
    with pytest.raises(ValueError):
        poisson_arrivals(0.0, 4)


def test_burst_arrivals_shape():
    a = burst_arrivals(10, burst=4, gap_s=0.1)
    assert list(a[:4]) == [0.0] * 4
    assert list(a[4:8]) == [pytest.approx(0.1)] * 4
    assert list(a[8:]) == [pytest.approx(0.2)] * 2
    with pytest.raises(ValueError):
        burst_arrivals(4, burst=0, gap_s=0.1)


def test_auto_rate(ref_served):
    _, _, x, ps = ref_served
    rate, unit_us = auto_rate(ps, x.shape[1:], utilization=0.5, reps=3)
    assert unit_us > 0
    assert rate == pytest.approx(0.5 * ps.buckets[-1] / (unit_us / 1e6))


# ------------------------------------------- data-parallel mesh serving
@pytest.mark.slow
def test_mesh_data_parallel_serve_matches_single_device():
    """2x2 make_test_mesh: the server's batch-axis-sharded dispatch is
    bit-identical to single-device serving (subprocess with 8 fake host
    devices, like test_distributed)."""
    code = textwrap.dedent("""
    import dataclasses, json
    import jax, jax.numpy as jnp, numpy as np
    from repro.configs import smoke_cnn_config
    from repro.launch.mesh import make_test_mesh
    from repro.launch.server import CNNServer
    from repro.models.cnn import SparseCNN

    assert len(jax.devices()) == 8
    cfg = dataclasses.replace(
        smoke_cnn_config("sparse-cnn-tiny", sparsity=0.625), kernel_mode="pallas"
    )
    model = SparseCNN(cfg)
    params = model.compress(model.init(jax.random.PRNGKey(0)))
    x = jax.random.normal(jax.random.PRNGKey(1), (8, 16, 16, 3))
    _, stats = model.apply(params, x[:4], collect_act_stats=True)
    qparams = model.quantize(params, stats)

    # dp=2 (the mesh's data axis): every bucket shards evenly
    plan_set = model.plan_set(qparams, max_batch=8, dp=2)
    assert plan_set.buckets == (2, 4, 8)
    single = np.asarray(plan_set.serve(x))          # single-device reference

    mesh = make_test_mesh((2, 2))
    pool = np.asarray(x)
    srv = CNNServer(plan_set, max_wait_ms=50.0, mesh=mesh)
    with srv:
        srv.warmup(x.shape[1:])
        futs = [srv.submit(pool[i:i+1]) for i in range(8)]
        out = np.concatenate([f.result(timeout=120) for f in futs])
        ragged = srv.serve_batch(pool[:5])          # pads 5 -> bucket 8, DP-sharded
    identical = bool((out == single).all()) and bool(
        (np.asarray(ragged) == single[:5]).all())
    print(json.dumps({
        "identical": identical,
        "retraces": srv.retraces_after_warmup,
        "buckets": list(plan_set.buckets),
    }))
    """)
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = str(REPO / "src")
    out = subprocess.run(
        [sys.executable, "-c", code], capture_output=True, text=True, env=env,
        timeout=540,
    )
    assert out.returncode == 0, f"STDOUT:\n{out.stdout}\nSTDERR:\n{out.stderr[-3000:]}"
    r = json.loads(out.stdout.strip().splitlines()[-1])
    assert r["identical"], r
    assert r["retraces"] == 0, r
