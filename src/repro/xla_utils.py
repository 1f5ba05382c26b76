"""Small helpers over jax compiled-artifact introspection APIs, plus the
shared wall-time measurement harness (``benchmarks/timing.py`` re-exports
it, so benchmark and serving-driver numbers come from one code path).

Measurement statistics (DESIGN.md §12): on a shared/contended host,
scheduling noise is strictly *additive* — a sample is the true cost plus
whatever the OS stole — so the **min** over many repetitions estimates
the true cost far more stably than the median of a few (profiling on a
noisy CPU showed medians of 7 swinging ±70% between batches while mins
of 30 stayed within ±3%). Comparisons between two programs should
additionally be **interleaved** (A, B, A, B, …) so environment drift
cancels out of the ratio: :func:`interleaved_time_us`.

:func:`use_compile_cache` places JAX's persistent compilation cache.
"""
from __future__ import annotations

import os
import pathlib
import statistics
import time

# <checkout>/.cache/jax — fixed, so a later run in the same checkout finds
# the programs an earlier one compiled (the path is part of the cache key)
CHECKOUT_CACHE_DIR = pathlib.Path(__file__).resolve().parents[2] / ".cache" / "jax"


def use_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache; returns its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and
    nothing is set here. Otherwise the cache goes to the fixed in-checkout
    :data:`CHECKOUT_CACHE_DIR` (listed in ``.gitignore``). Call it before
    the first compile."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax

    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE_DIR))
    return str(CHECKOUT_CACHE_DIR)

_STATS = ("median", "min", "p25", "mean")


def _reduce(samples, stat: str) -> float:
    if stat == "median":
        return statistics.median(samples)
    if stat == "min":
        return min(samples)
    if stat == "p25":
        s = sorted(samples)
        return s[max(0, (len(s) - 1) // 4)]
    if stat == "mean":
        return statistics.fmean(samples)
    raise ValueError(f"stat must be one of {_STATS}, got {stat!r}")


def time_samples_us(fn, *args, warmup: int = 1, reps: int = 5) -> list:
    """Raw per-call wall-time samples of ``fn(*args)`` in microseconds.

    ``warmup`` un-timed calls absorb compilation/tracing, then ``reps``
    timed calls each wrapped in ``jax.block_until_ready`` (imported lazily
    so this module stays importable without jax for plain-python callers).
    """
    import jax

    for _ in range(max(0, warmup)):
        jax.block_until_ready(fn(*args))
    samples = []
    for _ in range(max(1, reps)):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        samples.append((time.perf_counter() - t0) * 1e6)
    return samples


def median_time_us(fn, *args, warmup: int = 1, reps: int = 5,
                   stat: str = "median") -> float:
    """Wall time of ``fn(*args)`` in microseconds — ``stat`` over ``reps``
    timed calls after ``warmup`` un-timed ones.

    The default statistic stays the median (the historical contract every
    caller was written against); pass ``stat='min'`` with a larger
    ``reps`` for noise-robust gating comparisons (see module docstring).
    """
    return _reduce(time_samples_us(fn, *args, warmup=warmup, reps=reps), stat)


def interleaved_samples_us(fn_a, fn_b, *, warmup: int = 1, reps: int = 5):
    """``(a_samples, b_samples)`` raw µs wall times of two nullary
    callables sampled alternately (A, B, A, B, …), so environment drift
    cancels out of any derived comparison. The sample-level primitive
    under :func:`interleaved_time_us`; use it directly when you also
    need :func:`noise_frac` of the same batch (the regression gates)."""
    import jax

    for _ in range(max(0, warmup)):
        jax.block_until_ready(fn_a())
        jax.block_until_ready(fn_b())
    sa, sb = [], []
    for _ in range(max(1, reps)):
        t0 = time.perf_counter()
        jax.block_until_ready(fn_a())
        sa.append((time.perf_counter() - t0) * 1e6)
        t0 = time.perf_counter()
        jax.block_until_ready(fn_b())
        sb.append((time.perf_counter() - t0) * 1e6)
    return sa, sb


def interleaved_time_us(fn_a, fn_b, *, warmup: int = 1, reps: int = 5,
                        stat: str = "median"):
    """``(a_us, b_us)`` wall times of two nullary callables sampled
    alternately (A, B, A, B, …) — the canonical harness for any paired
    perf claim (fused-vs-unfused gates, plan-vs-unplanned).

    ``stat='min'`` over many reps is the noise-robust choice for gating
    (additive-noise argument in the module docstring); ``'median'`` is
    the default.
    """
    sa, sb = interleaved_samples_us(fn_a, fn_b, warmup=warmup, reps=reps)
    return _reduce(sa, stat), _reduce(sb, stat)


def noise_frac(samples) -> float:
    """Relative measurement-noise estimate of a sample batch: how far the
    lower quartile sits above the min, ``(p25 - min) / min``. Near 0 on a
    quiet host, large when scheduling noise contaminates even the fast
    samples — the self-calibration term the measured-wall-time regression
    gates widen their margins by (DESIGN.md §12)."""
    lo = min(samples)
    if lo <= 0:
        return 0.0
    return max(0.0, _reduce(samples, "p25") / lo - 1.0)


def cost_analysis_dict(compiled) -> dict:
    """The compiled program's cost-analysis properties (flops, bytes
    accessed, ...) as a dict."""
    return compiled.cost_analysis()


def hlo_op_breakdown(fn, *args) -> dict:
    """Kernel-launch-level attribution of a jitted program (DESIGN.md §12).

    Compiles ``fn(*args)`` and parses the optimized HLO: per-opcode
    instruction counts, the number of fusion computations and custom
    calls (≈ kernel launches on CPU/GPU backends), plus the normalized
    cost-analysis properties. This is how ``benchmarks/perf/
    profile_fused.py`` shows *where* a wall-time delta between two
    programs comes from without a hardware profiler.
    """
    import collections
    import re

    import jax

    compiled = jax.jit(fn).lower(*args).compile()
    text = compiled.as_text()
    ops: collections.Counter = collections.Counter()
    for line in text.splitlines():
        # instruction lines look like: "  %name = type opcode(...)" or
        # "  ROOT %name = type opcode(...)"
        m = re.match(r"\s*(?:ROOT\s+)?%?[\w.\-]+\s*=\s*\S+\s+([a-z][\w\-]*)\(", line)
        if m:
            ops[m.group(1)] += 1
    cost = cost_analysis_dict(compiled)
    return {
        "ops": dict(ops),
        "n_instructions": int(sum(ops.values())),
        "n_fusions": int(ops.get("fusion", 0)),
        "n_custom_calls": int(ops.get("custom-call", 0)),
        "bytes_accessed": cost.get("bytes accessed"),
        "flops": cost.get("flops"),
    }
