"""The end-to-end and host-side readers, on windows made by hand."""
import importlib.util
import types

import pytest

import traffic
from conftest import BENCH


def reader(name):
    spec = importlib.util.spec_from_file_location(name, BENCH / "metrics" / f"{name}.py")
    m = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(m)
    return m


def window(n, sched, sent, done, error=None):
    w = traffic.Window()
    w.n, w.start, w.sched, w.sent = n, [0] * len(n), sched, sent
    w.done, w.error = done, error or {}
    w.t0, w.t_end = 100.0, 102.0
    return types.SimpleNamespace(window=w)


def test_images_per_s_counts_what_completed_inside_the_window():
    run = window([128, 128, 128], [0, 0, 0], [100.0, 100.5, 101.9],
                 {0: 100.4, 1: 101.0, 2: 102.3})
    assert reader("images_per_s").read(run) == 256 / 2.0


def test_latency_is_timed_from_the_schedule_and_missing_is_late():
    n = 200  # one missing of 200 stays beyond the 99th percentile
    run = window([1] * n, [i * 0.01 for i in range(n)], [100 + i * 0.01 for i in range(n)],
                 {i: 100 + i * 0.01 + 0.005 for i in range(n - 1)}, {n - 1: "NeverCame"})
    assert reader("latency_p50_ms").read(run) == pytest.approx(5.0)
    assert reader("latency_p99_ms").read(run) == pytest.approx(5.0)
    run.window.error = {i: "RuntimeError" for i in range(n - 3, n)}
    assert reader("latency_p99_ms").read(run) is None  # 1.5 % missing: the tail is


def test_gen_lag_and_batch_fill():
    run = window([1] * 4, [0.0, 0.1, 0.2, 0.3], [100.0, 100.1, 100.203, 100.3], {})
    assert reader("gen_lag_p99_ms").read(run) == pytest.approx(3.0)
    stats = types.SimpleNamespace(served_samples=64, padded_samples=16)
    assert reader("batch_fill").read(types.SimpleNamespace(stats=stats)) == 75.0
