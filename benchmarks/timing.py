"""Shared wall-time measurement for every ``bench_*`` module.

One harness — warmup (absorbs compile/trace), ``jax.block_until_ready``
around each timed call, a configurable statistic over k repetitions. The
canonical implementation lives in :mod:`repro.xla_utils`, which the
program's own serving drivers time through too.

Measurement policy (DESIGN.md §12): single numbers use
:func:`median_time_us`; any *paired* perf claim (fused vs unfused, plan
vs unplanned) must use :func:`interleaved_time_us`
with ``stat='min'`` and generous reps — on shared CI hosts, scheduling
noise is additive and non-interleaved medians of a few samples routinely
invert comparisons (the PR-6-era ``BENCH_fused.json`` "regression" was
exactly this artifact).
"""
from repro.xla_utils import (  # noqa: F401
    interleaved_samples_us,
    interleaved_time_us,
    median_time_us,
    noise_frac,
    time_samples_us,
)
