"""What the serving path records about itself (DESIGN.md §11, §14).

- Host spans (``jax.profiler.TraceAnnotation``): under a profiler, every
  dispatched batch is one ``serve.batch`` span on the dispatcher thread
  holding ``serve.assemble`` and ``plan.dispatch`` (itself holding
  ``plan.launch``), and one ``plan.fetch`` and one ``serve.complete`` on
  the completion thread; warm-up marks ``plan.warmup`` per bucket, clients
  ``serve.submit``, the idle dispatcher ``serve.wait``, and the garbage
  collector ``host.gc``.
- Counters (``ServerStats``, always on): queue wait per dispatched request,
  warm-up seconds, garbage-collector passes.
- Stage scopes (``jax.named_scope``) name each stage's ops in the compiled
  program without changing what it computes or how often it traces.

A small ``sparse-cnn-s`` on the reference kernels, on the CPU.
"""
import dataclasses
import functools
import gc
import glob
import os
import re
from concurrent.futures import wait

import jax
import numpy as np
import pytest
from jax.profiler import ProfileData

from repro.configs import smoke_cnn_config
from repro.launch.faults import FaultInjected, FaultInjector
from repro.launch.server import CNNServer, DeadlineExceeded
from repro.models.cnn import SparseCNN

SPANS = ("serve.submit", "serve.wait", "serve.batch", "serve.assemble",
         "serve.complete", "plan.dispatch", "plan.launch", "plan.fetch",
         "plan.warmup", "host.gc")


@pytest.fixture(scope="module")
def served():
    cfg = dataclasses.replace(smoke_cnn_config("sparse-cnn-s", sparsity=0.625),
                              kernel_mode="ref")
    model = SparseCNN(cfg)
    params = model.compress(model.init(jax.random.PRNGKey(0)))
    x = jax.random.normal(jax.random.PRNGKey(1),
                          (16, cfg.image_size, cfg.image_size, cfg.in_channels))
    _, stats = model.apply(params, x[:4], collect_act_stats=True)
    plan_set = model.plan_set(model.quantize(params, stats), max_batch=8)
    return np.asarray(x), plan_set


def _spans(trace_dir) -> list:
    """(thread, name, start_ns, end_ns, attributes) of the serving spans;
    a thread is its line's name and place (every Python thread's line is
    named alike)."""
    (path,) = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*",
                                     "*.xplane.pb"))
    out = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name != "/host:CPU":
            continue
        for i, line in enumerate(plane.lines):
            for e in line.events:
                if e.name in SPANS:
                    out.append((f"{line.name}/{i}", e.name, e.start_ns,
                                e.start_ns + e.duration_ns, dict(e.stats)))
    return out


def _inside(spans, outer, name) -> list:
    thread, _, lo, hi, _ = outer
    return [s for s in spans
            if s[0] == thread and s[1] == name and lo <= s[2] and s[3] <= hi]


def test_serving_spans_nest_once_per_batch(served, tmp_path):
    x, plan_set = served
    srv = CNNServer(plan_set, max_wait_ms=2.0)
    sizes = [1, 3, 8, 2, 5, 1, 4]
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        with srv:
            srv.warmup()
            futs = [srv.submit(x[:k]) for k in sizes]
            for f in futs:
                f.result(timeout=120)
            gc.collect()
    finally:
        jax.profiler.stop_trace()
    spans = _spans(tmp_path)
    named = lambda name: [s for s in spans if s[1] == name]  # noqa: E731

    batches = named("serve.batch")
    assert len(batches) == srv.stats.batches > 0  # every request fits a bucket
    assert sum(b[4]["requests"] for b in batches) == len(sizes)
    assert sum(b[4]["samples"] for b in batches) == sum(sizes)
    dispatches = []
    for batch in batches:
        for name in ("serve.assemble", "plan.dispatch"):
            assert len(_inside(spans, batch, name)) == 1, name
        (dispatch,) = _inside(spans, batch, "plan.dispatch")
        assert dispatch[4]["bucket"] in plan_set.buckets
        assert 1 <= dispatch[4]["n_real"] <= dispatch[4]["bucket"]
        assert len(_inside(spans, dispatch, "plan.launch")) == 1
        for name in ("plan.fetch", "serve.complete"):  # not on this thread
            assert not _inside(spans, batch, name), name
        dispatches.append(dispatch)
    # one fetch and one completion per batch, in launch order, on the
    # completion thread: batch k's fetch has its bucket, and its
    # completion follows that fetch
    first = lambda rows: sorted(rows, key=lambda s: s[2])  # noqa: E731
    serving = [s for s in named("plan.fetch") if s[0] != named("plan.warmup")[0][0]]
    fetches, completes = first(serving), first(named("serve.complete"))
    assert len(fetches) == len(completes) == len(batches)
    assert ([f[4]["bucket"] for f in fetches]
            == [d[4]["bucket"] for d in first(dispatches)])
    assert ([c[4]["requests"] for c in completes]
            == [b[4]["requests"] for b in first(batches)])
    for fetch, complete in zip(fetches, completes):
        assert fetch[0] == complete[0] and fetch[3] <= complete[2]
    warm = named("plan.warmup")
    assert sorted(s[4]["bucket"] for s in warm) == list(plan_set.buckets)
    assert len(named("serve.submit")) == len(sizes)
    assert named("serve.wait")
    assert named("host.gc") and all("generation" in s[4] for s in named("host.gc"))
    assert srv.stats.gc_collections >= 1 and srv.stats.gc_s > 0


def test_server_host_counters(served):
    x, plan_set = served
    faults = FaultInjector()
    poison = faults.poison(x[9:10].copy())  # fails inside its dispatch
    srv = CNNServer(plan_set, max_wait_ms=2.0, faults=faults)
    with srv:
        srv.warmup()
        futs = [srv.submit(x[i:i + 2]) for i in range(0, 8, 2)]
        futs.append(srv.submit(poison))
        late = srv.submit(x[:1], deadline_s=1e-6)  # expires, never dispatched
        wait(futs + [late], timeout=120)
    s = srv.stats
    s.assert_accounting()
    assert isinstance(late.exception(), DeadlineExceeded)
    completed = sum(f.exception() is None for f in futs)
    failed = sum(isinstance(f.exception(), FaultInjected) for f in futs)
    assert (completed, failed) == (4, 1)
    assert s.dispatched_requests == completed + failed
    assert s.queue_wait_s >= 0
    assert s.warmup_s > 0
    summary = s.summary()
    for key in ("queue_wait_s", "dispatched_requests", "warmup_s",
                "gc_collections", "gc_s", "overlapped_batches"):
        assert summary[key] == getattr(s, key)
    assert "mean_us" not in summary


def test_gc_hook_only_while_running(served):
    _, plan_set = served
    srv = CNNServer(plan_set, max_wait_ms=2.0)
    with srv:
        assert srv._on_gc in gc.callbacks
        gc.collect()
        n = srv.stats.gc_collections
        assert n >= 1
    assert srv._on_gc not in gc.callbacks
    gc.collect()
    assert srv.stats.gc_collections == n


def _unscoped(plan):
    """The plan's chain as it ran before stages were scoped."""
    return jax.jit(lambda v: functools.reduce(lambda a, l: l.run(a),
                                              plan.layers, v))


def test_stage_scopes_name_every_stage(served):
    x, plan_set = served
    plan = plan_set.plans[8]
    text = plan.lower(x[:8]).compile().as_text()
    scoped = {m.split("/")[1] for m in re.findall(r'op_name="(jit\(chain\)/[^"]*)"', text)}
    assert {l.name for l in plan.layers} <= scoped


def test_stage_scopes_keep_logits_and_traces(served):
    """No profiler: served logits bit-identical to the unscoped chain, and
    no retrace after warm-up."""
    x, plan_set = served
    sizes = [3, 1, 8, 4]
    with CNNServer(plan_set, max_wait_ms=1.0) as srv:
        srv.warmup()
        ys, off = [], 0
        for k in sizes:
            ys.append((off, k, srv.submit(x[off:off + k]).result(timeout=120)))
            off += k
    assert srv.retraces_after_warmup == 0
    for off, k, y in ys:
        b = plan_set.bucket_for(k)
        xb = np.zeros((b,) + x.shape[1:], x.dtype)
        xb[:k] = x[off:off + k]
        np.testing.assert_array_equal(
            y, np.asarray(_unscoped(plan_set.plans[b])(xb))[:k])
