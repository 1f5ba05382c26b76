"""The shared wall-time measurement harness (DESIGN.md §12).

Deterministic fake-clock tests of :mod:`repro.xla_utils` — median-of-k
semantics, warmup exclusion, every supported statistic, interleaved
(A, B, A, B, …) sample alternation, and the noise estimator. No real
timing: the clock is a scripted ``perf_counter`` and
``jax.block_until_ready`` is a recorder, so the tests pin the harness
*contract* without inheriting host noise.
"""
import pytest

from repro import xla_utils


class FakeTime:
    """Scripted ``perf_counter``: each timed sample consumes one duration
    (µs) from the queue — first call opens the sample, second closes it."""

    def __init__(self, durations_us):
        self.durations = list(durations_us)
        self._now = 0.0
        self._open = None

    def perf_counter(self):
        if self._open is None:
            self._open = self.durations.pop(0) * 1e-6
            return self._now
        self._now += self._open
        self._open = None
        return self._now


class Recorder:
    """Counts ``jax.block_until_ready`` calls (and passes values through)."""

    def __init__(self):
        self.calls = 0

    def __call__(self, value):
        self.calls += 1
        return value


@pytest.fixture()
def clock(monkeypatch):
    def install(durations_us):
        fake = FakeTime(durations_us)
        monkeypatch.setattr(xla_utils, "time", fake)
        return fake

    return install


@pytest.fixture()
def block(monkeypatch):
    import jax

    rec = Recorder()
    monkeypatch.setattr(jax, "block_until_ready", rec)
    return rec


class TestTimeSamples:
    def test_median_of_k_and_warmup_exclusion(self, clock, block):
        fake = clock([100.0, 300.0, 200.0])
        calls = []
        t = xla_utils.median_time_us(lambda: calls.append(1), warmup=2, reps=3)
        assert t == pytest.approx(200.0)          # median of {100, 300, 200}
        assert len(calls) == 5                    # warmup runs the fn...
        assert block.calls == 5                   # ...and blocks on it
        assert fake.durations == []               # ...but consumes no sample

    def test_min_stat(self, clock, block):
        clock([500.0, 90.0, 400.0])
        t = xla_utils.median_time_us(lambda: None, warmup=0, reps=3, stat="min")
        assert t == pytest.approx(90.0)

    def test_p25_and_mean(self, clock, block):
        clock([400.0, 100.0, 300.0, 200.0])
        samples = xla_utils.time_samples_us(lambda: None, warmup=0, reps=4)
        assert samples == pytest.approx([400.0, 100.0, 300.0, 200.0])
        assert xla_utils._reduce(samples, "p25") == pytest.approx(100.0)
        assert xla_utils._reduce(samples, "mean") == pytest.approx(250.0)

    def test_unknown_stat_raises(self):
        with pytest.raises(ValueError, match="stat"):
            xla_utils._reduce([1.0], "p999")

    def test_args_forwarded(self, clock, block):
        clock([10.0])
        got = []
        xla_utils.time_samples_us(lambda a, b: got.append((a, b)),
                                  "x", 7, warmup=0, reps=1)
        assert got == [("x", 7)]


class TestInterleaved:
    def test_alternation_and_sample_routing(self, clock, block):
        """Samples are taken A, B, A, B, … and land in the right batch."""
        clock([10.0, 20.0, 30.0, 40.0])
        order = []
        sa, sb = xla_utils.interleaved_samples_us(
            lambda: order.append("a"), lambda: order.append("b"),
            warmup=1, reps=2,
        )
        assert order == ["a", "b", "a", "b", "a", "b"]  # warmup pair first
        assert sa == pytest.approx([10.0, 30.0])
        assert sb == pytest.approx([20.0, 40.0])

    def test_stat_reduction(self, clock, block):
        clock([10.0, 20.0, 30.0, 40.0])
        a, b = xla_utils.interleaved_time_us(
            lambda: None, lambda: None, warmup=0, reps=2, stat="min")
        assert (a, b) == (pytest.approx(10.0), pytest.approx(20.0))


class TestNoiseFrac:
    def test_quiet_host_is_zero(self):
        assert xla_utils.noise_frac([100.0, 100.0, 100.0, 100.0]) == 0.0

    def test_contaminated_batch(self):
        # min 100, p25 of 8 sorted samples -> index 1 -> 150
        samples = [100.0, 150.0, 200.0, 250.0, 300.0, 350.0, 400.0, 450.0]
        assert xla_utils.noise_frac(samples) == pytest.approx(0.5)

    def test_nonpositive_min_guard(self):
        assert xla_utils.noise_frac([0.0, 10.0]) == 0.0
