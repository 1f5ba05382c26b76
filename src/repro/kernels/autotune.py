"""Empirical tile autotuner + persistent config cache (DESIGN.md §10).

The paper's headline numbers come from a *design-space search* over
interrelated tiling/reuse parameters (§V — the same methodology as S2TA
and the original Systolic Tensor Array DSE): enumerate the candidate
design points, prune with an analytic cost model, and measure what
survives. This module is that loop applied to the software datapath's own
free parameters — the Pallas launch tiles ``(bm, bn, kb)`` for the matmul
kernels and ``(bf, tile_h, tile_w)`` for the fused convs:

1. **enumerate** valid candidates per (kernel kind, launch signature) —
   matmul M/N tiles may be non-divisors thanks to the ops-layer
   pad-to-tile path; K-block and conv tiles stay exact divisors;
2. **prune** with the analytic roofline model (compute vs HBM traffic
   from ``dbb_gemm_costs``/``dbb_conv_costs``, tile-revisit factors, and
   a per-grid-step overhead term), keeping the top-K;
3. **measure** the survivors (plus the default tiles, always)
   with the shared ``block_until_ready`` median-of-k harness
   (``repro.xla_utils.median_time_us`` — the same code path
   ``benchmarks/timing.py`` uses, so tuner and benchmark numbers are
   comparable); the measured-best config wins;
4. **persist** winners in a versioned on-disk JSON cache keyed by
   (backend, kernel kind, shape signature), so repeat runs and CI are
   search-free, and **install** them into the ``kernels.core`` registry
   that the kernel entry points consult for default tiles.

``SparseCNN.plan()`` drives this once per model to build a frozen serving
plan (``repro.models.plan``); steady-state serving then does zero
per-call tile resolution.
"""
from __future__ import annotations

import dataclasses
import json
import os
import pathlib
from typing import Optional

import jax
import jax.numpy as jnp

from repro.core.energy_model import TPU_V5E
from repro.core.quant import dynamic_act_scale, quantize, quantize_dbb
from repro.core.vdbb import (
    DBBFormat,
    DENSE,
    dbb_encode,
    dbb_encode_conv,
    dbb_gemm_costs,
)
from repro.kernels import core, ops
from repro.xla_utils import interleaved_time_us, median_time_us

CACHE_VERSION = 1

# Roofline constants for the analytic pruning model. Absolute numbers do
# not matter (only the candidate ranking does). The machine balance
# defaults to the shared TPU-v5e constants in the energy model, plus a
# per-grid-step overhead term that penalizes pathologically fine grids
# (which is also what dominates interpret-mode timing on CPU) — but the
# per-backend *measured* calibration (``repro.kernels.calibrate``,
# DESIGN.md §12) overrides all three once fitted, so the pruning ranking
# tracks the machine the search actually runs on.
_PEAK_MACS = TPU_V5E["peak_bf16_flops"] / 2
_HBM_BW = TPU_V5E["hbm_bw"]
_STEP_OVERHEAD_S = 2e-6


# ---------------------------------------------------------------------------
# Persistent cache
# ---------------------------------------------------------------------------


def default_cache_path() -> pathlib.Path:
    env = os.environ.get("REPRO_AUTOTUNE_CACHE")
    if env:
        return pathlib.Path(env)
    return pathlib.Path.home() / ".cache" / "repro" / "autotune.json"


def cache_key(kind: str, sig: tuple, backend: Optional[str] = None) -> str:
    """Deterministic cache key: ``backend|kind|sig...`` — measured configs
    never cross backends (a CPU interpret-mode winner is meaningless on
    TPU), kernels, or launch shapes."""
    backend = backend or jax.default_backend()
    return f"{backend}|{kind}|" + "x".join(str(s) for s in sig)


class TuneCache:
    """Versioned on-disk JSON cache of measured-best tile configs.

    A version mismatch (or an unreadable file) invalidates the whole
    cache — entries are measurements, not correctness data, so silently
    dropping them is always safe.
    """

    def __init__(self, path=None):
        self.path = pathlib.Path(path) if path is not None else default_cache_path()
        self.entries: dict = {}
        # per-backend roofline calibration (repro.kernels.calibrate,
        # DESIGN.md §12) rides in the same file under its own
        # CALIBRATION_VERSION, invalidated independently of tile entries
        self.calibration: dict = {}
        self.load()

    def load(self) -> None:
        self.entries = {}
        self.calibration = {}
        try:
            data = json.loads(self.path.read_text())
        except (OSError, ValueError):
            return
        if not isinstance(data, dict) or data.get("version") != CACHE_VERSION:
            return  # version mismatch: invalidate, re-search on demand
        self.entries = dict(data.get("entries", {}))
        cal = data.get("calibration", {})
        self.calibration = dict(cal) if isinstance(cal, dict) else {}

    def get(self, key: str) -> Optional[dict]:
        return self.entries.get(key)

    def put(self, key: str, entry: dict) -> None:
        self.entries[key] = entry

    def save(self) -> None:
        import tempfile

        self.path.parent.mkdir(parents=True, exist_ok=True)
        # unique temp name: concurrent writers must not interleave into the
        # same staging file (last atomic rename wins, never a torn file)
        fd, tmp = tempfile.mkstemp(dir=self.path.parent,
                                   prefix=self.path.name + ".")
        with os.fdopen(fd, "w") as f:
            f.write(json.dumps(
                {"version": CACHE_VERSION, "entries": self.entries,
                 "calibration": self.calibration},
                indent=2, sort_keys=True,
            ))
        os.replace(tmp, self.path)


def _as_cache(cache) -> TuneCache:
    return cache if isinstance(cache, TuneCache) else TuneCache(cache)


def clear_op_caches() -> None:
    """Drop the jit caches of the ops entry points, so the next call
    re-resolves default tiles against the current registry state.
    (``core.set_tuned``/``core.clear_tuned`` already do this through the
    registered invalidation hook; this is the manual escape hatch.)"""
    ops._drop_jit_caches()


def install(kind: str, sig: tuple, tiles: dict) -> None:
    """Install a tile config into the kernel-core registry. The registry
    invalidates the ops jit caches itself on any actual change (and skips
    the invalidation for identical re-installs, e.g. cache replays), so
    already-traced default-tile launches re-consult it."""
    core.set_tuned(kind, sig, tiles)


# ---------------------------------------------------------------------------
# Candidate enumeration
# ---------------------------------------------------------------------------


def _spread(vals, keep: int):
    """At most ``keep`` values, evenly spread, endpoints always kept."""
    vals = sorted(set(vals))
    if len(vals) <= keep:
        return vals
    if keep <= 1:
        return [vals[-1]]  # the largest tile (fewest grid steps)
    step = (len(vals) - 1) / (keep - 1)
    return sorted({vals[round(i * step)] for i in range(keep)})


def _divisors(dim: int):
    return [d for d in range(1, dim + 1) if dim % d == 0]


def _mn_tile_pool(dim: int, default: int, keep: int = 5):
    """M/N tile candidates: powers of two (pad-to-tile makes non-divisors
    legal), useful divisors, the whole dimension, and the pick_tile
    default."""
    pool = {d for d in (8, 16, 32, 64, 128, 256, 512) if d <= dim}
    pool |= {d for d in _divisors(dim) if d >= max(2, default // 8)}
    pool.add(dim)
    pool.add(core.pick_tile(dim, default))
    return _spread(pool, keep)


def matmul_candidates(m: int, k: int, n: int, fmt: DBBFormat, keep: int = 5):
    """Valid ``(bm, bn, kb)`` dicts for one compressed-matmul launch."""
    nb = k // fmt.bz
    kbs = _spread([d for d in _divisors(nb)], 4)
    out = []
    for bm in _mn_tile_pool(m, 128, keep):
        for bn in _mn_tile_pool(n, 256, keep):
            for kb in kbs:
                out.append({"bm": bm, "bn": bn, "kb": kb})
    return out


def conv_candidates(ho: int, wo: int, f: int, keep: int = 4):
    """Valid ``(bf, tile_h, tile_w)`` dicts — conv tiles stay exact
    divisors (spatial geometry and the F BlockSpec have no pad path)."""
    bfs = _spread([d for d in _divisors(f) if d >= min(8, f)] or [f], keep)
    ths = _spread(_divisors(ho), 3)
    tws = _spread(_divisors(wo), 3)
    return [{"bf": bf, "tile_h": th, "tile_w": tw}
            for bf in bfs for th in ths for tw in tws]


# ---------------------------------------------------------------------------
# Analytic pruning model (roofline over the §5/§6 cost accounting)
# ---------------------------------------------------------------------------


def matmul_cost_terms(m: int, k: int, n: int, fmt: DBBFormat, tiles: dict,
                      itemsize: float = 4.0) -> tuple:
    """``(executed_macs, hbm_bytes, grid_steps)`` of one OS matmul launch
    under a tile config — the three roofline terms, shared by the modeled
    cost below and the calibration fit (``repro.kernels.calibrate``).

    A tiles are re-read once per N tile, the compressed weight stream once
    per M tile (output-stationary dataflow); padded candidates are charged
    their wasted compute.
    """
    bm, bn, kb = tiles["bm"], tiles["bn"], tiles["kb"]
    mp = -(-m // bm) * bm
    n_pad = -(-n // bn) * bn
    nb = max(k // fmt.bz, 1)
    grid = (mp // bm) * (n_pad // bn) * max(nb // kb, 1)
    c = dbb_gemm_costs(m, k, n, fmt, bits=int(8 * itemsize),
                       act_bits=int(8 * itemsize))
    act = c["act_bytes"] * (n_pad // bn) * (mp / m)
    wt = c["weight_bytes"] * (mp // bm)
    out = m * n * 4
    macs = c["executed_macs"] * ((mp * n_pad) / (m * n))
    return macs, act + wt + out, grid


def conv_cost_terms(batch: int, ho: int, wo: int, c_in: int, f: int,
                    kh: int, kw: int, sh: int, sw: int, fmt: DBBFormat,
                    tiles: dict, itemsize: float = 4.0) -> tuple:
    """Conv twin of :func:`matmul_cost_terms`."""
    bf, bh, bw = tiles["bf"], tiles["tile_h"], tiles["tile_w"]
    th, tw = ho // bh, wo // bw
    bh_in = (bh - 1) * sh + kh
    bw_in = (bw - 1) * sw + kw
    spatial = batch * th * tw
    grid = spatial * (f // bf)  # the kh·kw taps run inside each step
    g = dbb_gemm_costs(batch * ho * wo, kh * kw * c_in, f, fmt,
                       bits=int(8 * itemsize), act_bits=int(8 * itemsize))
    act = spatial * bh_in * bw_in * c_in * itemsize * (f // bf)
    wt = g["weight_bytes"] * spatial
    out = batch * ho * wo * f * 4
    return g["executed_macs"], act + wt + out, grid


def _resolve_cal(cal):
    """The calibration the modeled costs run under: an explicit
    :class:`repro.kernels.calibrate.Calibration`, else this backend's
    active/cached/default one (lazy import — no cycle)."""
    if cal is not None:
        return cal
    from repro.kernels import calibrate

    return calibrate.get_calibration()


def modeled_matmul_cost(m: int, k: int, n: int, fmt: DBBFormat, tiles: dict,
                        itemsize: float = 4.0, cal=None) -> float:
    """Modeled seconds for one OS matmul launch under a tile config:
    ``max(compute, memory) + grid · step_overhead`` with the per-backend
    calibrated machine constants (DESIGN.md §12)."""
    cal = _resolve_cal(cal)
    macs, bytes_, grid = matmul_cost_terms(m, k, n, fmt, tiles, itemsize)
    return (max(macs / cal.peak_macs, bytes_ / cal.hbm_bw)
            + grid * cal.step_overhead_s)


def modeled_conv_cost(batch: int, ho: int, wo: int, c_in: int, f: int,
                      kh: int, kw: int, sh: int, sw: int, fmt: DBBFormat,
                      tiles: dict, itemsize: float = 4.0, cal=None) -> float:
    """Modeled seconds for one fused-conv launch under a tile config."""
    cal = _resolve_cal(cal)
    macs, bytes_, grid = conv_cost_terms(batch, ho, wo, c_in, f, kh, kw,
                                         sh, sw, fmt, tiles, itemsize)
    return (max(macs / cal.peak_macs, bytes_ / cal.hbm_bw)
            + grid * cal.step_overhead_s)


# ---------------------------------------------------------------------------
# Search
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class TuneResult:
    """Outcome of one tuning query (searched, or replayed from cache)."""

    kind: str
    sig: tuple
    tiles: dict            # measured-best config
    measured_us: float     # its median wall time
    default_tiles: dict    # the untuned defaults (the baseline)
    default_us: float      # baseline median wall time (same harness/run)
    modeled_best_us: float     # best modeled cost over all candidates
    modeled_default_us: float  # modeled cost of the baseline
    n_candidates: int
    source: str            # 'search' | 'cache'

    @property
    def speedup(self) -> float:
        return self.default_us / max(self.measured_us, 1e-9)


# A searched winner must beat the default by this factor in the interleaved
# confirmation pass, or it is demoted back to the default — noisy shared-CPU
# measurements must never persist a config that is really a tie or a loss.
CONFIRM_MARGIN = 1.05


def interleaved_medians(fn_a, fn_b, *, warmup: int = 1, reps: int = 5,
                        stat: str = "median"):
    """Wall times (us) of two nullary callables sampled alternately
    (A, B, A, B, …), so environment drift cancels out of the comparison —
    the harness for winner-vs-default confirmation and for benchmarks.
    Delegates to the canonical :func:`repro.xla_utils.interleaved_time_us`
    (one code path for tuner, calibration, and benchmark comparisons);
    ``stat='min'`` over generous reps is the noise-robust gating choice."""
    return interleaved_time_us(fn_a, fn_b, warmup=warmup, reps=reps, stat=stat)


def _search(kind, sig, candidates, cost_fn, build, default_tiles, *,
            top_k, reps, warmup, cache, save):
    cands = [dict(t) for t in candidates]
    if default_tiles not in cands:
        cands.append(dict(default_tiles))
    ranked = sorted(cands, key=cost_fn)
    survivors = ranked[: max(1, top_k)]
    if default_tiles not in survivors:
        survivors.append(default_tiles)  # the baseline is always measured
    timed = [(median_time_us(build(t), warmup=warmup, reps=reps), t)
             for t in survivors]
    best_us, best = min(timed, key=lambda p: p[0])
    default_us = next(us for us, t in timed if t == default_tiles)
    if best != default_tiles:
        # confirmation pass: the apparent winner must replicate its win
        # head-to-head against the default, beyond the noise margin
        b_us, d_us = interleaved_medians(
            build(best), build(default_tiles), warmup=1, reps=max(reps, 3)
        )
        if b_us * CONFIRM_MARGIN <= d_us:
            best_us, default_us = b_us, d_us
        else:
            best, best_us, default_us = dict(default_tiles), d_us, d_us
    res = TuneResult(
        kind=kind, sig=sig, tiles=best, measured_us=best_us,
        default_tiles=default_tiles, default_us=default_us,
        modeled_best_us=cost_fn(ranked[0]) * 1e6,
        modeled_default_us=cost_fn(default_tiles) * 1e6,
        n_candidates=len(cands), source="search",
    )
    install(kind, sig, best)
    if cache is not None:
        cache.put(cache_key(kind, sig), _entry(res))
        if save:
            cache.save()
    return res


def _entry(res: TuneResult) -> dict:
    return {
        "tiles": res.tiles, "measured_us": res.measured_us,
        "default_tiles": res.default_tiles, "default_us": res.default_us,
        "modeled_best_us": res.modeled_best_us,
        "modeled_default_us": res.modeled_default_us,
        "n_candidates": res.n_candidates,
    }


def _from_entry(kind, sig, e: dict) -> TuneResult:
    return TuneResult(
        kind=kind, sig=sig, tiles=dict(e["tiles"]),
        measured_us=e["measured_us"], default_tiles=dict(e["default_tiles"]),
        default_us=e["default_us"], modeled_best_us=e["modeled_best_us"],
        modeled_default_us=e["modeled_default_us"],
        n_candidates=e["n_candidates"], source="cache",
    )


def _matmul_kind(fmt: DBBFormat, n: int) -> str:
    return core.KIND_MATMUL_TC if fmt.group_size(n) == n else core.KIND_MATMUL_BW


def tune_matmul(m: int, k: int, n: int, fmt: DBBFormat, *,
                dtype=jnp.float32, top_k: int = 4, reps: int = 3,
                warmup: int = 1, keep: int = 5, cache=None, save: bool = True,
                force: bool = False, seed: int = 0) -> TuneResult:
    """Measured-best ``(bm, bn, kb)`` for one compressed-matmul launch.

    Cache hits skip the search entirely (``force=True`` re-measures); the
    winner is installed into the kernel-core registry either way, so
    subsequent default-tile ``ops.vdbb_matmul``/``ops.quant_matmul`` calls
    at this signature use it.
    """
    kind = _matmul_kind(fmt, n)
    sig = core.matmul_sig(m, k, n, fmt.bz, fmt.nnz, dtype)
    cache = _as_cache(cache)
    if not force:
        hit = cache.get(cache_key(kind, sig))
        if hit is not None:
            install(kind, sig, hit["tiles"])
            return _from_entry(kind, sig, hit)
    k1, k2 = jax.random.split(jax.random.PRNGKey(seed))
    a = jax.random.normal(k1, (m, k), jnp.float32)
    dw = dbb_encode(jax.random.normal(k2, (k, n), jnp.float32), fmt, prune=True)
    if jnp.dtype(dtype) == jnp.int8:
        a = quantize(a, dynamic_act_scale(a))
        dw = quantize_dbb(dw).as_dbb()
    elif jnp.dtype(dtype) != jnp.float32:
        a = a.astype(dtype)
        dw = dataclasses.replace(dw, values=dw.values.astype(dtype))
    itemsize = float(jnp.dtype(dtype).itemsize)

    def build(t):
        return lambda: ops.vdbb_matmul(a, dw, bm=t["bm"], bn=t["bn"], kb=t["kb"])

    from repro.kernels import calibrate

    cal = calibrate.get_calibration(cache=cache)  # per-backend pruning (§12)
    return _search(
        kind, sig, matmul_candidates(m, k, n, fmt, keep=keep),
        lambda t: modeled_matmul_cost(m, k, n, fmt, t, itemsize, cal=cal),
        build, core.default_matmul_tiles(m, k, n, fmt.bz, dtype),
        top_k=top_k, reps=reps, warmup=warmup, cache=cache, save=save,
    )


def tune_conv(batch: int, h: int, w: int, c: int, f: int, kh: int, kw: int,
              fmt: Optional[DBBFormat] = None, *, stride=1, padding="SAME",
              dtype=jnp.float32, top_k: int = 4, reps: int = 3,
              warmup: int = 1, keep: int = 4, cache=None, save: bool = True,
              force: bool = False, seed: int = 0) -> TuneResult:
    """Measured-best ``(bf, tile_h, tile_w)`` for one fused-conv launch.

    ``fmt=None`` tunes the dense im2col kernel; a sparse format tunes the
    fused IM2COL × VDBB kernel in its tc/bw mode.
    """
    (sh, sw), _, (ho, wo) = core.conv_geometry(h, w, kh, kw, stride, padding)
    if fmt is None:
        kind = core.KIND_CONV_DENSE
        sig = core.conv_sig(batch, ho, wo, c, f, kh, kw, sh, sw, 0, 0, dtype)
    else:
        kind = (core.KIND_CONV_TC if fmt.group_size(f) == f
                else core.KIND_CONV_BW)
        sig = core.conv_sig(batch, ho, wo, c, f, kh, kw, sh, sw,
                            fmt.bz, fmt.nnz, dtype)
    cache = _as_cache(cache)
    if not force:
        hit = cache.get(cache_key(kind, sig))
        if hit is not None:
            install(kind, sig, hit["tiles"])
            return _from_entry(kind, sig, hit)
    k1, k2 = jax.random.split(jax.random.PRNGKey(seed))
    x = jax.random.normal(k1, (batch, h, w, c), jnp.float32)
    w4 = jax.random.normal(k2, (kh, kw, c, f), jnp.float32)
    if jnp.dtype(dtype) == jnp.int8:
        x = quantize(x, dynamic_act_scale(x))
    elif jnp.dtype(dtype) != jnp.float32:
        x = x.astype(dtype)
        w4 = w4.astype(dtype)
    if fmt is None:
        wd = w4 if jnp.dtype(dtype) != jnp.int8 else quantize(
            w4, dynamic_act_scale(w4))

        def build(t):
            return lambda: ops.fused_im2col_conv(
                x, wd, stride=stride, padding=padding, bf=t["bf"],
                tile_h=t["tile_h"], tile_w=t["tile_w"])
    else:
        dw = dbb_encode_conv(jax.random.normal(k2, (kh, kw, c, f), jnp.float32),
                             fmt, prune=True)
        if jnp.dtype(dtype) == jnp.int8:
            dw = quantize_dbb(dw).as_dbb()

        def build(t):
            return lambda: ops.sparse_conv(
                x, dw, kh, kw, stride=stride, padding=padding, bf=t["bf"],
                tile_h=t["tile_h"], tile_w=t["tile_w"])

    itemsize = float(jnp.dtype(dtype).itemsize)
    mfmt = fmt or DENSE

    from repro.kernels import calibrate

    cal = calibrate.get_calibration(cache=cache)  # per-backend pruning (§12)
    return _search(
        kind, sig, conv_candidates(ho, wo, f, keep=keep),
        lambda t: modeled_conv_cost(batch, ho, wo, c, f, kh, kw, sh, sw,
                                    mfmt, t, itemsize, cal=cal),
        build, core.default_conv_tiles(ho, wo, f, kh, kw),
        top_k=top_k, reps=reps, warmup=warmup, cache=cache, save=save,
    )


# ---------------------------------------------------------------------------
# Plan-time resolution (registry → cache → optional search)
# ---------------------------------------------------------------------------


def tiles_for_matmul(m, k, n, fmt, dtype, *, mode: str = "cache", cache=None,
                     top_k: int = 4, reps: int = 3) -> dict:
    """Resolve tiles for a matmul launch under a tuning ``mode``:
    ``'off'`` (the default tiles), ``'cache'`` (registry/cache hits only,
    never search), ``'search'`` (search on miss and persist)."""
    if mode == "off":
        return {}
    kind = _matmul_kind(fmt, n)
    sig = core.matmul_sig(m, k, n, fmt.bz, fmt.nnz, dtype)
    t = core.lookup_tiles(kind, sig)
    if t:
        return dict(t)
    cache = _as_cache(cache)
    hit = cache.get(cache_key(kind, sig))
    if hit is not None:
        install(kind, sig, hit["tiles"])
        return dict(hit["tiles"])
    if mode != "search":
        return {}
    return dict(tune_matmul(m, k, n, fmt, dtype=dtype, top_k=top_k,
                            reps=reps, cache=cache).tiles)


def tiles_for_conv(batch, h, w, c, f, kh, kw, fmt, dtype, *, stride=1,
                   padding="SAME", mode: str = "cache", cache=None,
                   top_k: int = 4, reps: int = 3) -> dict:
    """Conv twin of :func:`tiles_for_matmul` (``fmt=None`` = dense kernel)."""
    if mode == "off":
        return {}
    (sh, sw), _, (ho, wo) = core.conv_geometry(h, w, kh, kw, stride, padding)
    if fmt is None:
        kind, bz, nnz = core.KIND_CONV_DENSE, 0, 0
    else:
        kind = core.KIND_CONV_TC if fmt.group_size(f) == f else core.KIND_CONV_BW
        bz, nnz = fmt.bz, fmt.nnz
    sig = core.conv_sig(batch, ho, wo, c, f, kh, kw, sh, sw, bz, nnz, dtype)
    t = core.lookup_tiles(kind, sig)
    if t:
        return dict(t)
    cache = _as_cache(cache)
    hit = cache.get(cache_key(kind, sig))
    if hit is not None:
        install(kind, sig, hit["tiles"])
        return dict(hit["tiles"])
    if mode != "search":
        return {}
    return dict(tune_conv(batch, h, w, c, f, kh, kw, fmt, stride=stride,
                          padding=padding, dtype=dtype, top_k=top_k,
                          reps=reps, cache=cache).tiles)
