"""Pallas TPU kernel for the time-unrolled VDBB sparse matmul.

Two modes, mirroring DESIGN.md §2:

* ``tc`` (tile-coupled / group-shared patterns, ``fmt.group == 'matrix'``):
  the activation "mux" of the paper's S8DP1 lane becomes an in-VMEM one-hot
  contraction that builds a *compressed-K* activation tile; the MAC stream
  becomes a dense MXU matmul over K_c = K·nnz/bz. FLOPs *and* HBM weight
  bytes scale with nnz/bz, at full MXU utilization for any nnz — the
  "constant utilization, variable occupancy" property.

* ``bw`` (paper-faithful per-column patterns): compressed weights are
  expanded to a dense block inside VMEM right before the dot (the analogue
  of the mux sitting right before the MAC). HBM weight traffic scales with
  nnz/bz; compute stays dense. This is the variant that matches the ASIC's
  storage format bit-for-bit.

Both kernels are built on :mod:`repro.kernels.core` — the shared
output-stationary VMEM accumulator with the K-block grid dimension
innermost (the systolic array's output-stationary dataflow).

Both accept int8 operands (the ASIC's native precision, DESIGN.md §8):
integer inputs switch the whole pipeline — one-hot mux, MXU dots, OS
accumulator — to exact int32 arithmetic. The full layer epilogue fuses
into the accumulator flush (DESIGN.md §9): per-output-column ``scales``
(dequantization, int32 → fp32 · scale), ``bias``, ``relu``, and
``out_scale`` (requantize-to-int8 at the next layer's activation scale) —
exactly where the hardware's requantizer sits, so a whole serving layer
is one kernel with no standalone fp32 passes after it. Without any
epilogue the raw int32 accumulator is returned.

Tiling taxonomy (paper's A×B×C_M×N → BlockSpec): bm×bn is the TPE array
footprint (output tile), bz=B is the block size, kb is how many blocks
stream per grid step. Interpret mode (CPU validation) accepts any shapes;
the TPU compiler enforces, per block, a last dim that is a multiple of
128 or the whole array dim, and a second-to-last dim that is a multiple
of 8 or the whole dim. Hence the defaults: bn a multiple of 128 (the ops
layer pads N=1000 to 1024 rather than take bn=250), bm a multiple of 8
(32 for int8) or all of M, and kb·bz a multiple of 128 (kb=16 at bz=8)
or all of K. Inside the body the compiler refuses a reshape that splits
the lane axis, so the tc mux never forms a (bm, kb, bz) view of the A
tile: it is one 2-D selection matmul (``core.dbb_mux``), and the bw
expand is 2-D too.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core.vdbb import DBBFormat
from repro.kernels import core


def _check_compressed_operands(a, values, fmt):
    m, k = a.shape
    nb, nnz, n = values.shape
    if nb * fmt.bz != k:
        raise ValueError(f"K={k} != nb*bz = {nb}*{fmt.bz}")
    if nnz != fmt.nnz:
        raise ValueError(f"values nnz={nnz} != fmt.nnz={fmt.nnz}")
    return m, k, nb, n


def _resolve_tiles(a, m, k, n, fmt, bm, bn, kb):
    """``(bm, bn, kb)`` of one launch: explicit tiles must divide exactly;
    None takes the aligned default — bm a sublane multiple for the
    operand dtype, bn a lane multiple, kb from ``core.default_kb`` — or
    the whole dimension when no aligned divisor exists (a full-extent
    block is always legal)."""
    nb = k // fmt.bz
    return (
        core.resolve_or_pick(m, bm, 128, "bm", align=core.sublanes(a.dtype)),
        core.resolve_or_pick(n, bn, 256, "bn", align=core.LANES),
        core.resolve_tile(nb, core.default_kb(nb, fmt.bz) if kb is None else kb, "kb"),
    )


# ---------------------------------------------------------------------------
# tc mode: gather-compressed-K (group-shared pattern)
# ---------------------------------------------------------------------------


def _vdbb_tc_kernel(a_ref, v_ref, pos_ref, *rest, ep=None):
    """Grid: (M/bm, N/bn, NB/kb). a: (bm, kb*bz); v: (kb*nnz, bn);
    pos: (kb*nnz, 1) int32 — the A-tile column each compressed-K column
    reads (``core.mux_positions``); acc: (bm, bn) f32/i32 VMEM scratch;
    ``rest`` carries the optional (1, bn) fp32 epilogue rows named by the
    static ``ep`` (scale/bias/out_scale — DESIGN.md §9)."""
    flush, o_ref, acc_ref = core.split_epilogue(ep, rest)
    # The activation mux: A[:, pos[j]] -> the (bm, kb*nnz) compressed-K tile.
    ac = core.dbb_mux(a_ref[...], pos_ref[...])
    contrib = core.mxu_dot(ac, v_ref[...])
    core.os_accumulate(acc_ref, o_ref, contrib, grid_axis=2, **flush)


def vdbb_matmul_tc(
    a: jax.Array,
    values: jax.Array,
    indices: jax.Array,
    fmt: DBBFormat,
    *,
    scales: jax.Array | None = None,
    bias: jax.Array | None = None,
    relu: bool = False,
    out_scale=None,
    bm: int | None = None,
    bn: int | None = None,
    kb: int | None = None,
    out_dtype=None,
    interpret: bool | None = None,
) -> jax.Array:
    """A (M, K) × compressed W -> (M, N). values: (nb, nnz, N);
    indices: (nb, nnz) int (pattern shared across N). int8 operands
    accumulate in exact int32; ``scales`` (N,) / ``bias`` (N,) / ``relu``
    / ``out_scale`` (scalar or (N,)) fuse the layer epilogue into the
    accumulator flush (DESIGN.md §9; out int8 when requantizing). Default
    tiles are aligned divisors (``core.aligned_divisor``); explicit ones
    must divide exactly."""
    m, k, nb, n = _check_compressed_operands(a, values, fmt)
    bz, nnz = fmt.bz, fmt.nnz
    bm, bn, kb = _resolve_tiles(a, m, k, n, fmt, bm, bn, kb)
    v2 = values.reshape(nb * nnz, n)
    pos = core.mux_positions(indices, kb, bz)
    acc_dtype = core.acc_dtype_for(a.dtype)
    ep, e_ops, e_specs, out_dtype = core.epilogue_plan(
        n, bn, scales=scales, bias=bias, relu=relu, out_scale=out_scale,
        acc_dtype=acc_dtype, in_dtype=a.dtype, out_dtype=out_dtype,
    )
    return core.os_matmul_call(
        functools.partial(_vdbb_tc_kernel, ep=ep),
        (a, v2, pos, *e_ops),
        m=m,
        n=n,
        bm=bm,
        bn=bn,
        k_steps=nb // kb,
        in_specs=[
            pl.BlockSpec((bm, kb * bz), lambda i, j, s: (i, s)),
            pl.BlockSpec((kb * nnz, bn), lambda i, j, s: (s, j)),
            pl.BlockSpec((kb * nnz, 1), lambda i, j, s: (s, 0)),
            *e_specs,
        ],
        out_dtype=out_dtype,
        name="vdbb_matmul_tc",
        acc_dtype=acc_dtype,
        interpret=interpret,
    )


# ---------------------------------------------------------------------------
# bw mode: in-VMEM expand (paper-faithful per-column pattern)
# ---------------------------------------------------------------------------


def dbb_expand_block(v, idx, bz):
    """In-VMEM scatter-expand of a compressed block to dense (kb*bz, bn)
    — the "late mux" right before the MAC:
    wd[k*bz + i, n] = sum_j [idx[j, k, n] == i] * v[j, k, n].

    ``v``/``idx``: (nnz, kb, bn) (slot-major, as the wrappers lay them
    out). Each slot's (kb, bn) row block is repeated bz times down the
    sublanes by a one-hot (kb*bz, kb) matmul — exact, and 2-D throughout,
    so no lane or sublane axis is split. Dtype-preserving (positions
    within a block-column are distinct, so each output element receives
    at most one non-zero)."""
    nnz, kb, bn = v.shape
    rows = kb * bz
    r = jax.lax.broadcasted_iota(jnp.int32, (rows, kb), 0)
    c = jax.lax.broadcasted_iota(jnp.int32, (rows, kb), 1)
    rep = (r // bz == c).astype(jnp.float32)
    slot = (jax.lax.broadcasted_iota(jnp.int32, (rows, bn), 0) % bz).astype(jnp.float32)

    def repeat(x):
        return jax.lax.dot(rep, x.astype(jnp.float32),
                           precision=jax.lax.Precision.HIGHEST,
                           preferred_element_type=jnp.float32)

    wd = jnp.zeros((rows, bn), jnp.float32)
    for j in range(nnz):
        wd = wd + jnp.where(repeat(idx[j]) == slot, repeat(v[j]), 0.0)
    return wd.astype(v.dtype)


def _vdbb_bw_kernel(a_ref, v_ref, idx_ref, *rest, bz, ep=None):
    """Grid: (M/bm, N/bn, NB/kb). a: (bm, kb*bz); v/idx: (nnz, kb, bn)
    (idx int32) — per-column patterns; ``rest`` carries the optional
    (1, bn) fp32 epilogue rows named by ``ep`` (DESIGN.md §9)."""
    flush, o_ref, acc_ref = core.split_epilogue(ep, rest)
    wd = dbb_expand_block(v_ref[...], idx_ref[...], bz)
    contrib = core.mxu_dot(a_ref[...], wd)
    core.os_accumulate(acc_ref, o_ref, contrib, grid_axis=2, **flush)


def vdbb_matmul_bw(
    a: jax.Array,
    values: jax.Array,
    indices: jax.Array,
    fmt: DBBFormat,
    *,
    scales: jax.Array | None = None,
    bias: jax.Array | None = None,
    relu: bool = False,
    out_scale=None,
    bm: int | None = None,
    bn: int | None = None,
    kb: int | None = None,
    out_dtype=None,
    interpret: bool | None = None,
) -> jax.Array:
    """A (M, K) × compressed W -> (M, N). values/indices: (nb, nnz, N).
    int8 + epilogue (``scales``/``bias``/``relu``/``out_scale``) as in
    :func:`vdbb_matmul_tc`."""
    m, k, nb, n = _check_compressed_operands(a, values, fmt)
    bz, nnz = fmt.bz, fmt.nnz
    bm, bn, kb = _resolve_tiles(a, m, k, n, fmt, bm, bn, kb)
    v3 = values.transpose(1, 0, 2)  # (nnz, nb, N): slot-major
    idx3 = indices.astype(jnp.int32).transpose(1, 0, 2)
    acc_dtype = core.acc_dtype_for(a.dtype)
    ep, e_ops, e_specs, out_dtype = core.epilogue_plan(
        n, bn, scales=scales, bias=bias, relu=relu, out_scale=out_scale,
        acc_dtype=acc_dtype, in_dtype=a.dtype, out_dtype=out_dtype,
    )
    return core.os_matmul_call(
        functools.partial(_vdbb_bw_kernel, bz=bz, ep=ep),
        (a, v3, idx3, *e_ops),
        m=m,
        n=n,
        bm=bm,
        bn=bn,
        k_steps=nb // kb,
        in_specs=[
            pl.BlockSpec((bm, kb * bz), lambda i, j, s: (i, s)),
            pl.BlockSpec((nnz, kb, bn), lambda i, j, s: (0, s, j)),
            pl.BlockSpec((nnz, kb, bn), lambda i, j, s: (0, s, j)),
            *e_specs,
        ],
        out_dtype=out_dtype,
        name="vdbb_matmul_bw",
        acc_dtype=acc_dtype,
        interpret=interpret,
    )
