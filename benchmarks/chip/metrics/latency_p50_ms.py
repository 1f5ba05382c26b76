"""Median latency of all requests sent in the window (see ``_latency``)."""
import _latency


def read(run):
    return _latency.percentile(run, 50)
