"""The readers of the program's own spans and counters
(``dispatch_host_ms``, ``queue_wait_ms``, ``warmup_s``) on traces and stats
made by hand, and on five batches of sparse-cnn-s.d3of8 bucket 128
recorded on a TPU v5e with the program's spans and stage scopes in place
(trimmed). Beside the keys ``devtrace.read_xplane`` makes, that recording
keeps ``scopes``: per chip, each device op's name path (the ``tf_op`` stat
of its event metadata, in the order of ``devices``), which shows that the
stage scopes cover the device's work; the harness does not read it."""
import gzip
import importlib.util
import json
import types

import pytest

import devtrace
from conftest import BENCH

OLD = BENCH / "tests" / "data" / "trace_offline_d3of8.json.gz"
SCOPED = BENCH / "tests" / "data" / "trace_offline_d3of8_scoped.json.gz"
STAGES = ["l0", "l1", "l2", "l3", "l4", "l5", "l6", "l7", "gap", "l8"]
MS = 1_000_000  # ns


def reader(name):
    spec = importlib.util.spec_from_file_location(name, BENCH / "metrics" / f"{name}.py")
    m = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(m)
    return m.read


def load(path):
    with gzip.open(path, "rt") as f:
        return json.load(f)


def by_hand_trace():
    """Three batches on the dispatcher thread ``d``: one chunk, two chunks,
    one chunk; one more batch that runs past the window's end, and a
    ``plan.fetch`` on another thread inside the second batch."""
    host = [
        ["m", "bench.window", 0, 100 * MS],
        ["d", "serve.batch", 10 * MS, 20 * MS],
        ["d", "serve.assemble", 10 * MS, 2 * MS],
        ["d", "plan.dispatch", 12 * MS, 15 * MS],
        ["d", "plan.launch", 12 * MS, 3 * MS],
        ["d", "plan.fetch", 16 * MS, 10 * MS],
        ["d", "serve.batch", 40 * MS, 30 * MS],
        ["d", "plan.dispatch", 41 * MS, 10 * MS],
        ["d", "plan.fetch", 45 * MS, 5 * MS],
        ["d", "plan.dispatch", 52 * MS, 16 * MS],
        ["d", "plan.fetch", 55 * MS, 10 * MS],
        ["w", "plan.fetch", 41 * MS, 3 * MS],
        ["d", "serve.batch", 80 * MS, 10 * MS],
        ["d", "plan.fetch", 82 * MS, 4 * MS],
        ["d", "serve.batch", 95 * MS, 10 * MS],
        ["d", "plan.fetch", 96 * MS, 8 * MS],
    ]
    ops = [["a", 10, 10], ["b", 20, 5], ["c", 30, 10], ["d", 60, 10]]
    return {"window": [0, 100 * MS], "devices": {"/device:TPU:0": ops}, "host": host}


def test_dispatch_host_ms_subtracts_fetches_inside_each_batch():
    read = reader("dispatch_host_ms")
    # batches in the window: 20 − 10, 30 − (5 + 10), 10 − 4 ms; median 10
    assert read(types.SimpleNamespace(trace=by_hand_trace())) == pytest.approx(10.0)
    assert read(types.SimpleNamespace(trace=None)) is None
    assert read(types.SimpleNamespace(trace=load(OLD))) is None  # no program spans


def test_queue_wait_ms_and_warmup_s_from_the_counters():
    stats = types.SimpleNamespace(queue_wait_s=0.5, dispatched_requests=40, warmup_s=8.5)
    run = types.SimpleNamespace(stats=stats, trace=None)
    assert reader("queue_wait_ms")(run) == pytest.approx(12.5)
    assert reader("warmup_s")(run) == 8.5
    stats.dispatched_requests = 0
    assert reader("queue_wait_ms")(run) is None
    older = types.SimpleNamespace(stats=types.SimpleNamespace(batches=3), trace=None)
    assert reader("queue_wait_ms")(older) is None  # a server without the counters
    assert reader("warmup_s")(older) is None


@pytest.fixture(scope="module")
def scoped():
    return load(SCOPED)


def test_recorded_trace_carries_every_stage(scoped):
    """Every stage has device time in the window, and at most a tenth of
    the busy time lies outside every stage."""
    lo, hi = scoped["window"]
    (dev, ops), = scoped["devices"].items()
    paths = scoped["scopes"][dev]

    def stage_s(stages):
        mine = [e for e, path in zip(ops, paths) if stages & set(path.split("/"))]
        return sum(b - a for a, b in devtrace.merged(mine, lo, hi)) / 1e9

    assert all(stage_s({s}) > 0 for s in STAGES)
    busy = devtrace.busy_s(scoped)
    assert 0.90 * busy <= stage_s(set(STAGES)) <= busy + 1e-9


def test_recorded_trace_program_spans(scoped):
    host = scoped["host"]
    batches = [e for e in host if e[1] == "serve.batch"]
    assert len(batches) == 5
    assert 0 < reader("dispatch_host_ms")(types.SimpleNamespace(trace=scoped)) < 50
