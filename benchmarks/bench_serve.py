"""Continuous-batching serving tier under load (DESIGN.md §11).

Drives the §11 queue → bucketer → frozen-plan pipeline with a load
generator and writes ``BENCH_serve.json`` (a CI artifact gated by
``benchmarks/check_regression.py``). Six claims, all measured:

1. **Bit-exactness**: bucketed/padded serving of every ragged batch size
   (including one larger than the biggest bucket, which chunks) equals
   per-request ``plan.serve`` exactly.
2. **Wall time is the contract** (first slice of the ROADMAP item): the
   frozen bucket plan is not slower than the *jitted-once* unplanned
   ``model.apply`` beyond the committed noise margin — a fair baseline,
   unlike comparing against an unjitted per-call lambda.
3. **Zero retraces after warmup**: sustained variable-batch Poisson and
   burst traffic dispatches only pre-compiled bucket plans; the plans'
   own trace counters must not move during the load run.
4. **Latency under load**: p50/p99 request latency (arrival → result
   ready) and sustained throughput per arrival pattern, with a
   self-calibrating p99 bound — ``margin × (max_wait + (depth+2) ×
   measured_bucket_time)`` — so the gate tracks the host's speed
   instead of hardcoding microseconds (what it catches is the failure
   mode that matters: a retrace or batching regression inflating tail
   latency by orders of magnitude).
5. **Blast radius** (DESIGN.md §14): a full co-batch carrying a
   raise-poison and a nan-poison completes every innocent request
   bit-identical to a fault-free per-request serve; exactly the poisons
   get their typed exceptions; bisect isolation adds zero retraces.
6. **Overload**: 2x measured capacity into a bounded queue with reject
   shedding — sheds with a measured retry-after, admitted p99 stays
   within the (now exactly known: the admission cap) depth bound,
   goodput holds above ``chaos_goodput_floor`` x capacity, and the
   ``completed+rejected+failed+expired == offered`` books balance.
7. **Self-healing lifecycle** (DESIGN.md §15): three chaos scenarios
   through the ``Supervisor``. (a) a dispatcher kill under Poisson load
   — the supervised restart requeues every undispatched request and all
   of them complete bit-identical (survival 1.0, zero hung futures, one
   ``ServerStats`` balancing the books across the restart); (b) hot
   reload — a verified checkpoint swaps the plan set atomically
   mid-traffic, a *corrupted* latest checkpoint fails typed
   (``CorruptCheckpointError``) with the old plan still serving
   bit-identical, and ``fallback=True`` walks back to the newest
   verifiable step; (c) kernel degradation — a persistent compiled-path
   fault on one bucket demotes exactly that bucket to its bit-compatible
   ref fallback (innocent buckets untouched, every result still
   bit-identical), degraded-mode goodput holds above
   ``selfheal_goodput_floor`` x the healthy path's, and after the fault
   heals a recovery probe re-promotes the bucket.

Offered load is auto-picked at ~25% of measured capacity (conservative:
on the CPU smoke model, thread/GIL overhead per dispatch is comparable
to the 3–4ms compute itself, so higher offered fractions saturate the
interpreter, not the datapath).
"""
import json
import pathlib
import sys
import time
from concurrent.futures import TimeoutError as FutureTimeout

# Standalone-runnable (`python -m benchmarks.bench_serve --smoke`, the CI
# one-liner): put src/ on the path like benchmarks/run.py does.
_SRC = str(pathlib.Path(__file__).resolve().parents[1] / "src")
if _SRC not in sys.path:
    sys.path.insert(0, _SRC)

import jax
import jax.numpy as jnp
import numpy as np

from repro.launch.faults import FaultInjected, FaultInjector, \
    corrupt_checkpoint
from repro.launch.server import CNNServer, NumericalFault, Overloaded, \
    ServerCrashed, auto_rate, burst_arrivals, poisson_arrivals
from repro.launch.supervisor import Supervisor
from repro.xla_utils import interleaved_time_us, median_time_us

OUT_PATH = pathlib.Path(__file__).resolve().parents[1] / "BENCH_serve.json"
# Margins shared with benchmarks/check_regression.py via the committed
# baselines file — bench and CI gate can never silently disagree.
_BASELINES = json.loads(
    (pathlib.Path(__file__).resolve().parent / "bench_baselines.json").read_text()
)
PLAN_MARGIN = _BASELINES["serve_plan_margin"]   # plan vs jitted-unplanned
P99_MARGIN = _BASELINES["serve_p99_margin"]     # p99 vs self-calibrated bound
GOODPUT_FLOOR = _BASELINES["chaos_goodput_floor"]  # overload goodput/capacity
SELFHEAL_FLOOR = _BASELINES["selfheal_goodput_floor"]  # degraded vs healthy


def _drive(server, arrivals, xpool, sizes):
    """Submit per the arrival schedule (real sleeps), resolve all futures.

    The pool is sliced as numpy: a client hands the server host data, and
    on a single device a jax slice per submit would enqueue onto the same
    stream the serving batches run on and contend with them.
    """
    xpool = np.asarray(xpool)
    futures = []
    t0 = time.monotonic()
    pool = xpool.shape[0]
    for i, t_arr in enumerate(arrivals):
        lag = t_arr - (time.monotonic() - t0)
        if lag > 0:
            time.sleep(lag)
        j = i % (pool - 1)  # keep room for 2-sample requests at the edge
        futures.append(server.submit(xpool[j : j + sizes[i]]))
    return [f.result(timeout=300) for f in futures]


def run(report, smoke: bool = True):
    import dataclasses

    from repro.configs import smoke_cnn_config
    from repro.models.cnn import SparseCNN

    cfg = dataclasses.replace(
        smoke_cnn_config("sparse-cnn-tiny", sparsity=0.625), kernel_mode="pallas"
    )
    model = SparseCNN(cfg)
    params = model.compress(model.init(jax.random.PRNGKey(0)))
    sample_shape = (cfg.image_size, cfg.image_size, cfg.in_channels)
    xpool = jax.random.normal(jax.random.PRNGKey(1), (16,) + sample_shape)
    _, stats = model.apply(params, xpool[:4], collect_act_stats=True)
    qparams = model.quantize(params, stats)

    max_batch = 8
    plan_set = model.plan_set(qparams, max_batch=max_batch)
    plan_set.warmup(sample_shape)
    results = {
        "backend": jax.default_backend(),
        "buckets": list(plan_set.buckets),
        "patterns": {},
    }

    # --- 1. bucketed/padded serving == per-request plan.serve, exactly --
    for n in (1, 2, 3, 5, 8, 11):  # 11 > max bucket: exercises chunking
        got = plan_set.serve(xpool[:n])
        per = jnp.concatenate(
            [plan_set.plans[1].serve(xpool[i : i + 1]) for i in range(n)]
        )
        np.testing.assert_array_equal(np.asarray(got), np.asarray(per))
    results["bit_identical"] = True
    report("serve/bit_exact", 0.0,
           "ragged n in {1,2,3,5,8,11} pad/slice == per-request plan.serve")

    # --- 2. frozen bucket plan vs *jitted-once* unplanned apply ---------
    xb = xpool[:max_batch]
    unplanned = jax.jit(lambda x: model.apply(qparams, x))
    jax.block_until_ready(unplanned(xb))  # compile outside the timing
    plan_us, unplanned_us = interleaved_time_us(
        lambda: plan_set.plans[max_batch].serve(xb), lambda: unplanned(xb),
        warmup=2, reps=9,
    )
    assert plan_us <= unplanned_us * PLAN_MARGIN, (plan_us, unplanned_us)
    results["plan_us"] = round(plan_us, 1)
    results["unplanned_jit_us"] = round(unplanned_us, 1)
    report("serve/plan_vs_jitted_unplanned", plan_us,
           f"jitted-once unplanned {unplanned_us:.0f}us "
           f"({unplanned_us / max(plan_us, 1e-9):.2f}x, interleaved; "
           f"margin {PLAN_MARGIN}x is the wall-time contract)")

    # --- 3+4. load patterns through the server --------------------------
    rate, unit_us = auto_rate(plan_set, sample_shape, utilization=0.25)
    max_wait_ms = max(2.0, unit_us / 1e3)
    results["unit_us"] = round(unit_us, 1)
    results["max_wait_ms"] = round(max_wait_ms, 2)
    n_req = 48 if smoke else 192
    burst = 2 * max_batch
    patterns = {
        "poisson": (poisson_arrivals(rate, n_req, seed=7), 1),
        "burst": (burst_arrivals(n_req, burst=burst, gap_s=4 * unit_us / 1e6),
                  -(-burst // max_batch)),  # queue depth in buckets
    }
    rng = np.random.default_rng(11)
    for name, (arrivals, depth) in patterns.items():
        # mostly single-sample requests, a few 2-sample ones: the
        # aggregator must mix request sizes without splitting any
        sizes = np.where(rng.random(n_req) < 0.15, 2, 1)
        server = CNNServer(plan_set, max_wait_ms=max_wait_ms)
        with server:
            server.warmup(sample_shape)
            _drive(server, arrivals, xpool, sizes)
        retraces = server.retraces_after_warmup
        assert retraces == 0, f"{name}: {retraces} retraces under load"
        s = server.stats.summary()
        assert s["completed"] == s["offered"] == int(sizes.sum()), s
        bound_us = P99_MARGIN * (max_wait_ms * 1e3 + (depth + 2) * unit_us)
        assert s["p99_us"] <= bound_us, (name, s["p99_us"], bound_us)
        s.update(rate_rps=round(float(rate), 2),
                 retraces_after_warmup=retraces,
                 p99_bound_us=round(bound_us, 1))
        results["patterns"][name] = s
        report(f"serve/{name}_p99", s["p99_us"],
               f"p50 {s['p50_us']:.0f}us, {s['throughput_rps']:.1f} req/s "
               f"sustained, {s['batches']} batches {s['bucket_counts']}, "
               f"0 retraces after warmup")

    # --- 5. chaos: poison in a full co-batch, innocents survive ---------
    results["chaos"] = _chaos(report, plan_set, xpool, sample_shape,
                              max_batch, max_wait_ms)

    # --- 6. overload: 2x capacity offered, bounded queue sheds ----------
    results["overload"] = _overload(report, plan_set, xpool, sample_shape,
                                    max_batch, max_wait_ms, unit_us,
                                    smoke=smoke)

    # --- 7. self-healing lifecycle (§15): restart / reload / degrade ----
    results["selfheal"] = {
        "restart": _selfheal_restart(report, plan_set, xpool, sample_shape,
                                     rate, max_wait_ms),
        "reload": _selfheal_reload(report, model, qparams, plan_set, xpool,
                                   sample_shape, max_batch, max_wait_ms),
        "degraded": _selfheal_degraded(report, model, qparams, plan_set,
                                       xpool, sample_shape, max_wait_ms),
    }

    OUT_PATH.write_text(json.dumps(results, indent=2))
    report("serve/json", 0.0, f"wrote {OUT_PATH.name}")


def _selfheal_restart(report, plan_set, xpool, sample_shape, rate,
                      max_wait_ms):
    """§15 scenario (a): a dispatcher kill under Poisson load, recovered
    by a supervised restart. The kill seam fires mid-run with requests
    queued; the supervisor must restart the dispatcher, requeue every
    admitted-but-undispatched request, and *all* of them must complete
    bit-identical to a fault-free per-request serve — survival 1.0, zero
    hung futures, zero retraces (the plan set stays compiled across the
    restart), and one ``ServerStats`` whose
    ``completed+rejected+failed+expired == offered`` identity spans the
    whole supervised run."""
    pool = np.asarray(xpool)
    n_req = 32
    arrivals = poisson_arrivals(rate, n_req, seed=17)
    inj = FaultInjector(kill_after_dispatches=3, kills=1)
    srv = CNNServer(plan_set, max_wait_ms=max_wait_ms, faults=inj)
    sup = Supervisor(srv, backoff_s=0.01, backoff_max_s=0.1)
    ref = {i: np.asarray(plan_set.plans[1].serve(pool[i % pool.shape[0]][None]))
           for i in range(n_req)}
    futures, resubmits = [], 0
    t0 = time.monotonic()
    with sup:
        sup.warmup(sample_shape)
        for i, t_arr in enumerate(arrivals):
            lag = t_arr - (time.monotonic() - t0)
            if lag > 0:
                time.sleep(lag)
            while True:  # the restart gap: offered again, never dropped
                try:
                    futures.append(sup.submit(pool[i % pool.shape[0]][None]))
                    break
                except (ServerCrashed, RuntimeError):
                    resubmits += 1
                    assert resubmits < 2000, "restart gap never closed"
                    time.sleep(0.002)
        timeout_s = sup.request_timeout_s(floor_s=60.0)
        hung = survived = 0
        for i, f in enumerate(futures):
            try:
                y = np.asarray(f.result(timeout=timeout_s))
                survived += int(np.array_equal(y, ref[i]))
            except FutureTimeout:
                hung += 1
        elapsed = time.monotonic() - t0
        health = sup.health()
    sup.stats.assert_accounting()
    s = sup.stats.summary()
    out = {
        "restarts": s["restarts"],
        "requeued": s["requeued"],
        "survival": survived / n_req,      # bit-identical completions
        "hung": hung,
        "resubmits": resubmits,
        "accounting_ok": bool(s["accounting_ok"]),
        "retraces_after_warmup": sup.retraces_after_warmup,
        "injector_restarts": inj.restarts,
        "health": health["status"],
        "goodput_rps": round(s["completed"] / max(elapsed, 1e-9), 2),
    }
    assert out["restarts"] == 1 and inj.restarts == 1, out
    assert out["requeued"] >= 1, "kill with queued work requeued nothing"
    assert out["survival"] == 1.0 and hung == 0, out
    assert out["retraces_after_warmup"] == 0, out
    report("serve/selfheal_restart", 0.0,
           f"dispatcher killed mid-load: 1 supervised restart, "
           f"{s['requeued']} requeued, {n_req}/{n_req} bit-identical, "
           f"books balanced across the restart")
    return out


def _selfheal_reload(report, model, qparams, plan_set, xpool, sample_shape,
                     max_batch, max_wait_ms):
    """§15 scenario (b): hot checkpoint reload mid-traffic. A verified
    checkpoint swaps the plan set atomically (zero dropped requests,
    zero retraces after the swap — the supervisor warms off-thread); a
    *corrupted* latest checkpoint fails typed with the old plan still
    serving bit-identical; ``fallback=True`` walks back to the newest
    verifiable step and recovers."""
    import tempfile

    from repro.checkpoint.store import CorruptCheckpointError, save

    pool = np.asarray(xpool)
    ckpt_dir = tempfile.mkdtemp(prefix="bench-selfheal-ckpt-")
    save(ckpt_dir, 1, qparams)
    save(ckpt_dir, 2, qparams)
    srv = CNNServer(plan_set, max_wait_ms=max_wait_ms)
    sup = Supervisor(
        srv,
        rebuild=lambda tree: model.plan_set(tree, max_batch=max_batch),
        template=qparams,
    )
    out = {"hung": 0}
    with sup:
        sup.warmup(sample_shape)

        def probe():  # live traffic around every reload step
            ys = []
            for i in range(4):
                f = sup.submit(pool[i : i + 1])
                try:
                    ys.append(np.asarray(f.result(timeout=60)))
                except FutureTimeout:
                    out["hung"] += 1
            return ys

        y0 = probe()
        step, fp = sup.reload(ckpt_dir)         # clean: swap to step 2
        out["reload_step"] = step
        out["swap_bit_identical"] = all(
            np.array_equal(a, b) for a, b in zip(y0, probe()))
        corrupt_checkpoint(ckpt_dir, step=2, mode="flip")
        try:
            sup.reload(ckpt_dir)
            out["corrupt_typed"] = False        # must be unreachable
        except CorruptCheckpointError:
            out["corrupt_typed"] = True
        # the failed reload must leave the old plan serving, bit-identical
        out["old_plan_served"] = all(
            np.array_equal(a, b) for a, b in zip(y0, probe()))
        fb_step, _ = sup.reload(ckpt_dir, fallback=True)  # walk back
        out["fallback_step"] = fb_step
        out["fallback_recovered"] = bool(
            fb_step == 1
            and all(np.array_equal(a, b) for a, b in zip(y0, probe())))
        out["reloads"] = sup.stats.reloads
        out["reload_failures"] = sup.reload_failures
        out["retraces_after_warmup"] = sup.retraces_after_warmup
        out["health"] = sup.health()["status"]
    sup.stats.assert_accounting()
    out["accounting_ok"] = True
    assert out["reload_step"] == 2 and out["swap_bit_identical"], out
    assert out["corrupt_typed"] and out["old_plan_served"], out
    assert out["fallback_recovered"] and out["reloads"] == 2, out
    assert out["retraces_after_warmup"] == 0 and out["hung"] == 0, out
    report("serve/selfheal_reload", 0.0,
           "hot swap to step 2 (bit-identical, 0 retraces), corrupt step "
           "fails typed with old plan serving, fallback recovers step 1")
    return out


def _selfheal_degraded(report, model, qparams, plan_set, xpool, sample_shape,
                       max_wait_ms):
    """§15 scenario (c): persistent compiled-path fault on one bucket.
    With ``demote_after=1`` the first fault demotes exactly that bucket
    to its bit-compatible ref fallback — the faulted request itself is
    rescued (survival stays 1.0), innocent buckets keep their compiled
    plans bit-identical, degraded-mode goodput holds above
    ``selfheal_goodput_floor`` x the healthy path's (both measured in
    this run), and once the fault heals a recovery probe re-promotes."""
    pool = np.asarray(xpool)
    fallback = model.fallback_plan_set(qparams, plan_set)  # bit-compat asserted
    inj = FaultInjector()
    bad = 4  # the faulty bucket: 3-sample requests pad into it
    inj.fail_bucket(bad)
    srv = CNNServer(plan_set, max_wait_ms=max_wait_ms, faults=inj,
                    fallback=fallback, demote_after=1, probe_every=4)
    ref1 = [np.asarray(plan_set.plans[1].serve(pool[i : i + 1]))
            for i in range(8)]
    ref3 = [np.asarray(plan_set.serve(pool[i : i + 3])) for i in range(8)]

    def drive(n_samples, refs, count):  # serial: one request per dispatch
        ok = 0
        t0 = time.monotonic()
        for i in range(count):
            f = srv.submit(pool[i : i + n_samples])
            y = np.asarray(f.result(timeout=60))
            ok += int(np.array_equal(y, refs[i]))
        return ok, count * n_samples / max(time.monotonic() - t0, 1e-9)

    out = {}
    with srv:
        srv.warmup(sample_shape)
        # healthy baseline on an innocent bucket (compiled path)
        ok1, healthy_sps = drive(1, ref1, 8)
        # the faulty bucket: first dispatch faults -> demoted -> fallback
        ok3, degraded_sps = drive(3, ref3, 8)
        health_mid = srv.health()
        out["demoted"] = {str(b): r for b, r in srv.demoted_buckets().items()}
        # innocent bucket again while degraded: still compiled, bit-identical
        ok1b, _ = drive(1, ref1, 8)
        # heal the backend; the next recovery probe must re-promote
        inj.heal_bucket(bad)
        for i in range(8):
            f = srv.submit(pool[i : i + 3])
            np.testing.assert_array_equal(np.asarray(f.result(timeout=60)),
                                          ref3[i])
            if not srv.demoted_buckets():
                break
        health_end = srv.health()
    srv.stats.assert_accounting()
    s = srv.stats.summary()
    out.update({
        "survival": (ok1 + ok3 + ok1b) / 24,   # bit-identical completions
        "demoted_exact": list(out["demoted"]) == [str(bad)],
        "innocents_bit_identical": ok1 + ok1b == 16,
        "demotions": s["demotions"],
        "promotions": s["promotions"],
        "repromoted": not srv.demoted_buckets() and s["promotions"] == 1,
        "health_degraded": health_mid["status"],
        "health_recovered": health_end["status"],
        "bucket_faults_fired": inj.bucket_faults_fired,
        "healthy_sps": round(healthy_sps, 1),
        "degraded_sps": round(degraded_sps, 1),
        "accounting_ok": bool(s["accounting_ok"]),
    })
    assert out["survival"] == 1.0, out       # the faulted request is rescued
    assert out["demoted_exact"] and out["demotions"] == 1, out
    assert health_mid["status"] == "degraded" and str(bad) in out["demoted"], \
        health_mid
    assert out["repromoted"] and health_end["status"] == "ready", out
    assert degraded_sps >= SELFHEAL_FLOOR * healthy_sps, \
        f"degraded goodput {degraded_sps:.1f} < {SELFHEAL_FLOOR} x " \
        f"healthy {healthy_sps:.1f} samples/s"
    report("serve/selfheal_degraded", 0.0,
           f"bucket {bad} demoted to ref fallback on first fault "
           f"(reason recorded), 24/24 bit-identical, degraded "
           f"{degraded_sps:.0f} vs healthy {healthy_sps:.0f} samples/s, "
           f"probe re-promoted after heal")
    return out


def _chaos(report, plan_set, xpool, sample_shape, max_batch, max_wait_ms):
    """DESIGN.md §14 blast-radius gate: a slow plug request holds the
    dispatcher while a full ``max_batch`` co-batch queues up behind it,
    containing one raise-poison (plan exception at dispatch) and one
    nan-poison (NaN logits past the datapath). The bisect re-dispatch
    must complete every innocent **bit-identical** to a fault-free
    per-request serve, typed-fail exactly the two poisons, and add zero
    retraces (bisect halves land on already-warmed buckets)."""
    pool = np.asarray(xpool)
    inj = FaultInjector(slow_s=0.05)
    reqs = [pool[i : i + 1] for i in range(1 + max_batch)]  # plug + batch
    poison_raise = 1 + 2          # index 2 of the co-batch
    poison_nan = 1 + max_batch - 3
    inj.poison(reqs[poison_raise], "raise")
    inj.poison(reqs[poison_nan], "nan")
    # fault-free reference, served per-request outside the chaos server
    ref = {i: np.asarray(plan_set.plans[1].serve(r))
           for i, r in enumerate(reqs)
           if i not in (poison_raise, poison_nan)}

    server = CNNServer(plan_set, max_wait_ms=max_wait_ms, faults=inj)
    t0 = time.monotonic()
    with server:
        server.warmup(sample_shape)
        futures = [server.submit(reqs[0])]
        time.sleep(2 * max_wait_ms / 1e3)  # plug dispatches alone, slowly
        futures += [server.submit(r) for r in reqs[1:]]
        outcomes = {}
        for i, f in enumerate(futures):
            try:
                outcomes[i] = np.asarray(f.result(timeout=60))
            except (FaultInjected, NumericalFault) as e:
                outcomes[i] = e
    elapsed = time.monotonic() - t0
    server.stats.assert_accounting()

    survival = sum(
        1 for i in ref
        if isinstance(outcomes[i], np.ndarray)
        and np.array_equal(outcomes[i], ref[i])
    ) / len(ref)
    poison_typed = (isinstance(outcomes[poison_raise], FaultInjected)
                    and isinstance(outcomes[poison_nan], NumericalFault))
    retraces = server.retraces_after_warmup
    s = server.stats.summary()
    chaos = {
        "innocent_survival": survival,       # bit-identical completions
        "poison_typed": bool(poison_typed),  # exactly the poisons, typed
        "retraces_after_warmup": retraces,
        "accounting_ok": bool(s["accounting_ok"]),
        "goodput_rps": round(s["completed"] / max(elapsed, 1e-9), 2),
        "faults_fired": inj.faults_fired,
        "batches": s["batches"],
    }
    assert survival == 1.0, f"innocent survival {survival} (want 1.0)"
    assert poison_typed, {i: type(o).__name__ for i, o in outcomes.items()}
    assert retraces == 0, f"chaos bisect retraced {retraces}x"
    report("serve/chaos", 0.0,
           f"{len(ref)}/{len(ref)} innocents bit-identical beside 2 poisons "
           f"(bisect, {s['batches']} dispatches, 0 retraces)")
    return chaos


def _overload(report, plan_set, xpool, sample_shape, max_batch, max_wait_ms,
              unit_us, *, smoke):
    """DESIGN.md §14 overload gate: offer 2x measured capacity into a
    bounded queue (``2 x max_batch``) with reject shedding. The server
    must shed (``Overloaded`` with a measured retry-after), keep the
    admitted requests' p99 under the self-calibrated bound (queue depth
    is now *known*: the admission cap), balance the books exactly, and
    sustain goodput above the committed floor fraction of capacity."""
    pool = np.asarray(xpool)
    cap_rps, _ = auto_rate(plan_set, sample_shape, utilization=1.0)
    rate = 2.0 * cap_rps
    n_req = 96 if smoke else 384
    max_queue = 2 * max_batch
    arrivals = poisson_arrivals(rate, n_req, seed=13)
    server = CNNServer(plan_set, max_wait_ms=max_wait_ms,
                       max_queue=max_queue, shed="reject")
    shed = 0
    retry_after = 0.0
    t0 = time.monotonic()
    with server:
        server.warmup(sample_shape)
        futures = []
        for i, t_arr in enumerate(arrivals):
            lag = t_arr - (time.monotonic() - t0)
            if lag > 0:
                time.sleep(lag)
            try:
                futures.append(server.submit(pool[i % pool.shape[0]][None]))
            except Overloaded as e:
                shed += 1
                retry_after = e.retry_after_s
        timeout_s = server.request_timeout_s(floor_s=60.0)
        for f in futures:
            f.result(timeout=timeout_s)
        elapsed = time.monotonic() - t0
    server.stats.assert_accounting()
    s = server.stats.summary()
    assert s["rejected"] == shed and shed > 0, \
        f"2x capacity never shed (rejected={s['rejected']})"
    assert retry_after > 0.0, "Overloaded carried no measured retry-after"
    # queue depth is the admission cap: the p99 bound stops being a guess
    depth = -(-max_queue // max_batch)
    bound_us = P99_MARGIN * (max_wait_ms * 1e3 + (depth + 2) * unit_us)
    assert s["p99_us"] <= bound_us, (s["p99_us"], bound_us)
    goodput = s["completed"] / max(elapsed, 1e-9)
    floor = GOODPUT_FLOOR * cap_rps
    assert goodput >= floor, f"goodput {goodput:.1f} < floor {floor:.1f} rps"
    over = {
        "offered_rps": round(rate, 2),
        "capacity_rps": round(cap_rps, 2),
        "goodput_rps": round(goodput, 2),
        "shed_rate": s["shed_rate"],
        "rejected": s["rejected"],
        "completed": s["completed"],
        "offered": s["offered"],
        "retry_after_ms": round(retry_after * 1e3, 2),
        "p99_us": s["p99_us"],
        "p99_bound_us": round(bound_us, 1),
        "accounting_ok": bool(s["accounting_ok"]),
        "retraces_after_warmup": server.retraces_after_warmup,
    }
    report("serve/overload", s["p99_us"],
           f"2x capacity: shed {s['shed_rate']:.2f}, goodput "
           f"{goodput:.0f}/{cap_rps:.0f} rps capacity, p99 within bound, "
           f"books balanced {s['completed']}+{s['rejected']}+"
           f"{s['failed']}+{s['expired']}=={s['offered']}")
    return over


if __name__ == "__main__":
    import argparse

    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="CI-scale load (48 requests; default 192)")
    args = ap.parse_args()
    run(lambda name, us, derived="": print(f"{name},{us:.1f},{derived}"),
        smoke=args.smoke)
