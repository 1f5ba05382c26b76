"""The load generator: the same work for every seed, in another order."""
import concurrent.futures

import numpy as np
import pytest

import traffic

OPEN = {"loop": "open", "arrivals": "poisson", "rate_rps": 500.0,
        "request_images": 1, "pool_images": 16}


def test_every_seed_gets_the_same_gaps():
    a = traffic.arrivals(OPEN, 4.0, np.random.default_rng(1))
    b = traffic.arrivals(OPEN, 4.0, np.random.default_rng(2))
    assert a[0] == b[0] == 0.0 and a.max() < 4.0 and b.max() < 4.0
    assert len(a) == len(b) == 2000 and not np.array_equal(a, b)
    # one fixed set of gaps: all but the one after the last send are shared
    ga, gb = np.sort(np.diff(a)), np.sort(np.diff(b))
    nearest = np.abs(ga[:, None] - gb[None, :]).min(axis=1)
    assert (nearest < 1e-9).sum() >= len(ga) - 1


def test_unknown_arrivals_are_refused():
    with pytest.raises(ValueError):
        traffic.arrivals(dict(OPEN, arrivals="burst"), 1.0, np.random.default_rng(3))


class Echo:
    """Answers every request at once with its own rows."""

    def submit(self, x):
        f = concurrent.futures.Future()
        f.set_result(x.reshape(len(x), -1)[:, :2].copy())
        return f


@pytest.mark.parametrize("mix", [
    {"loop": "closed", "outstanding": 2, "request_images": 4, "pool_images": 16},
    OPEN,
])
def test_window_records_every_request(mix):
    pool = np.arange(16 * 3, dtype=np.float32).reshape(16, 3)
    win = traffic.run(Echo(), mix, pool, 0.3, seed=2**33 + 7)
    assert win.n and not win.error
    assert set(win.done) == set(range(len(win.n)))
    assert win.answers
    for i, y in win.answers.items():
        assert np.array_equal(y, pool[win.start[i]:win.start[i] + win.n[i], :2])
    again = traffic.run(Echo(), mix, pool, 0.3, seed=2**33 + 7)
    k = min(len(win.start), len(again.start))
    assert win.start[:k] == again.start[:k]  # the same seed, the same requests
