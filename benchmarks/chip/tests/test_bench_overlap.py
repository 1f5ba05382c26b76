"""The ``overlap_share`` reader on stats made by hand and on the program's
own ``ServerStats``: the share of batches launched while an earlier one
was still unfetched, and no reading from a server without the counter."""
import importlib.util
import types

import pytest

from conftest import BENCH
from repro.launch.server import ServerStats


def read(run):
    spec = importlib.util.spec_from_file_location(
        "overlap_share", BENCH / "metrics" / "overlap_share.py")
    m = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(m)
    return m.read(run)


@pytest.mark.parametrize("batches, overlapped, share", [
    (40, 38, 95.0),
    (40, 0, 0.0),
    (7, 7, 100.0),
    (0, 0, None),
])
def test_overlap_share_from_the_counter(batches, overlapped, share):
    stats = types.SimpleNamespace(batches=batches, overlapped_batches=overlapped)
    got = read(types.SimpleNamespace(stats=stats, trace=None))
    assert got == (None if share is None else pytest.approx(share))


def test_overlap_share_without_the_counter():
    older = types.SimpleNamespace(stats=types.SimpleNamespace(batches=3), trace=None)
    assert read(older) is None


def test_overlap_share_reads_server_stats():
    stats = ServerStats(batches=8, overlapped_batches=6)
    assert read(types.SimpleNamespace(stats=stats, trace=None)) == pytest.approx(75.0)
