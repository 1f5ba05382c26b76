"""Fused IM2COL × VDBB Pallas kernel — the paper's datapath, end-to-end.

This is the composition the paper's headline numbers come from: the
hardware IM2COL unit expands the activation stream *after* SRAM and feeds
it straight into the VDBB sparse tensor array. The TPU analogue fuses both
in-VMEM transforms in one kernel:

  HBM reads:  raw activation tile (once, + tile halo)  ×  compressed
              weight stream (nnz/bz of dense bytes)
  in VMEM:    shifted-view im2col tap (the IM2COL unit)
              → DBB gather (tc) or scatter-expand (bw) (the VDBB mux)
  compute:    MXU matmuls at nnz/bz occupancy (tc) or dense (bw)

The conv weight (kh, kw, C, F) is DBB-compressed along K = kh·kw·C with
C % bz == 0, so every bz-block lies inside a single kernel tap and tap
(dy, dx) reads exactly its own C/bz compressed blocks. Geometry, tiling,
and the epilogue all come from :mod:`repro.kernels.core` (DESIGN.md §6).

Tiling, as the TPU compiler enforces it: the grid is (output tile, F
block), and the F block is all of F (``core.default_bf``; smaller only
where a whole-F weight block would overflow VMEM). One step holds the
whole (kh·kw, cb·nnz, bf) compressed weight block, whose middle dim is
the whole array dim and so needs no alignment. The kh·kw taps are each
a static contiguous window of the stride-phase-split input tile (no
``dynamic_slice``, no strided 8-bit load). The mux is the 2-D selection
matmul ``core.dbb_mux`` — a ``(bh·bw, C) → (bh·bw, cb, bz)`` reshape
splits the lane axis, which the compiler refuses.

tc mode packs the compressed patch: the output rows go in static chunks
of about ``PACKED_PIXELS`` pixels; per chunk each tap's window goes
through the mux once, the taps' (rows, cb·nnz) compressed columns sit
side by side along the lanes of one (rows, kh·kw·cb·nnz) patch
(:func:`repro.kernels.im2col_conv.packed_patches`, as the dense conv
packs its raw taps), and one MXU dot contracts it against the weight
block viewed, inside the kernel, as (kh·kw·cb·nnz, bf). The result goes
straight into the chunk's flush: no accumulator scratch. A contraction
of K = cb·nnz (8–96 at sparse-cnn-s's widths) pays a whole 128-deep
pass per tap; packed, the nine taps share ⌈9·cb·nnz/128⌉. bw mode
accumulates one dense dot per tap in a VMEM scratch.

A 1×1 conv is the same kernel with one tap (a strided one reads its
input subsampled, ``plan_conv``); the int8 maps of ResNet-50 (56, 28,
14 and 7 wide) compile for v5e as they are, W unpadded
(``tests/test_tpu_compile.py``). Calls are named by kind
(:func:`kernel_name`): ``vdbb_im2col_conv_tc`` for a k×k conv,
``…_1x1`` for a pointwise one, ``…_res`` where the flush adds a residual.

Both pattern-sharing modes are provided, mirroring ``vdbb_matmul``:
``vdbb_im2col_conv_tc`` (group-shared patterns, compressed-K compute) and
``vdbb_im2col_conv_bw`` (paper-faithful per-column patterns, in-VMEM
expand). ``kernels.ops.sparse_conv`` dispatches on the weight's format.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.vdbb import DBBFormat, DBBWeight
from repro.kernels import core
from repro.kernels.im2col_conv import (
    conv_in_spec, conv_out_spec, conv_taps, packed_patches, plan_conv, tap_geom,
)
from repro.kernels.vdbb_matmul import dbb_expand_block


def _conv_weight_geometry(dw: DBBWeight, kh: int, kw: int):
    """Validate and split the compressed-K layout: K = kh·kw·C, C % bz == 0."""
    k, f = dw.shape
    bz = dw.fmt.bz
    if k % (kh * kw) != 0:
        raise ValueError(f"K={k} not divisible by kh*kw={kh * kw}")
    c = k // (kh * kw)
    if c % bz != 0:
        raise ValueError(
            f"C={c} not divisible by bz={bz}: a DBB block would straddle "
            "kernel taps, which the fused conv kernel does not support"
        )
    return c, f, c // bz


# ---------------------------------------------------------------------------
# tc mode: shifted view -> gather-compressed-K -> dense MXU dot
# ---------------------------------------------------------------------------


def _vdbb_conv_tc_kernel(x_ref, v_ref, pos_ref, *rest, geom, ep=None):
    """Grid: (N·th·tw, F/bf). x: (1, sh·sw, Hq, Wq, C); v: (kh·kw, cb·nnz,
    bf); pos: (kh·kw, cb·nnz, 1) int32 — per tap, the channel each
    compressed-K column reads; ``rest`` carries the optional (1, bf) fp32
    epilogue rows named by the static ``ep`` (scale/bias/out_scale —
    DESIGN.md §9), and a residual tile, sliced here to each chunk's rows.

    Per chunk of output rows, each tap's window goes through the mux
    once; the taps' compressed columns sit side by side along the lanes of
    one (rows, kh·kw·cb·nnz) patch (:func:`packed_patches`), contracted in
    one MXU dot against the weight block viewed as (kh·kw·cb·nnz, bf) —
    the row order of that view is the patch's lane order. No accumulator
    scratch: each chunk's result goes straight into its flush."""
    flush, o_ref, _ = core.split_epilogue(ep, (*rest, None))
    taps, ck, bf = v_ref.shape
    w = v_ref[...].reshape(taps * ck, bf)
    residual = flush.pop("residual", None)

    def mux(t, window):  # the activation mux: window[:, pos[t, j]] -> compressed K
        return core.dbb_mux(window, pos_ref[t])

    for r, rh, patch in packed_patches(x_ref, mux, **geom):
        if residual is not None:
            flush["residual"] = residual[:, r:r + rh]
        core.store_epilogue(core.mxu_dot(patch, w), o_ref.at[:, pl.ds(r, rh)], **flush)


# ---------------------------------------------------------------------------
# bw mode: shifted view -> in-VMEM scatter-expand -> dense MXU dot
# ---------------------------------------------------------------------------


def _vdbb_conv_bw_kernel(x_ref, v_ref, idx_ref, *rest, bz, geom, ep=None):
    """Grid: (N·th·tw, F/bf). x: (1, sh·sw, Hq, Wq, C); v/idx: (kh·kw,
    nnz, cb, bf) — per-column patterns, slot-major; ``rest`` carries the
    optional (1, bf) fp32 epilogue rows named by ``ep`` (DESIGN.md §9)."""
    flush, o_ref, acc_ref = core.split_epilogue(ep, rest)

    def tap(t, patch):
        wd = dbb_expand_block(v_ref[t], idx_ref[t], bz)  # (C, bf), the "late mux"
        return core.mxu_dot(patch, wd)

    conv_taps(x_ref, tap, acc_ref, **geom)
    core.store_epilogue(acc_ref[...], o_ref, **flush)


# ---------------------------------------------------------------------------
# host wrappers
# ---------------------------------------------------------------------------


def weight_block(values: jax.Array, fmt: DBBFormat) -> tuple:
    """``(k, itemsize)`` of the fused conv's weight block for compressed
    ``values`` (nb, nnz, F), as :func:`core.default_bf` sizes it: the kept
    rows, and the bytes an entry takes across the weight operands (in bw
    mode the int32 per-column indices ride beside the values)."""
    nb, nnz, f = values.shape
    return nb * nnz, values.dtype.itemsize + (0 if fmt.group_size(f) == f else 4)


def kernel_name(base: str, kh: int, kw: int, residual) -> str:
    """The Pallas call's name in a device profile: ``base``, with ``_1x1``
    for a pointwise conv and ``_res`` when the flush adds a residual, so a
    trace tells the 1×1, k×k and residual-fused calls apart."""
    return base + ("_1x1" if kh == kw == 1 else "") + ("_res" if residual is not None else "")


def _launch(kernel, name, x, operands, wspecs, kh, kw, *, stride, padding,
            bf, tile_h, tile_w, out_dtype, interpret, accumulate=False,
            scales=None, bias=None, relu=False, out_scale=None, residual=None,
            residual_scale=None):
    """Launch a fused conv; ``accumulate`` gives the kernel a (bh·bw, bf)
    accumulator scratch after its output ref (the per-tap bw kernel)."""
    n = x.shape[0]
    f = operands[0].shape[-1]
    xt, g = plan_conv(x, kh, kw, stride=stride, padding=padding,
                      tile_h=tile_h, tile_w=tile_w)
    grid = (n * g["th"] * g["tw"], f // bf)
    acc_dtype = core.acc_dtype_for(x.dtype)  # int32 on the int8 path
    out_spec = conv_out_spec(g, bf)
    ep, e_ops, e_specs, out_dtype = core.epilogue_plan(
        f, bf, scales=scales, bias=bias, relu=relu, out_scale=out_scale,
        acc_dtype=acc_dtype, in_dtype=x.dtype, out_dtype=out_dtype,
        residual=residual, residual_scale=residual_scale, residual_spec=out_spec,
    )
    operands = (*operands, *e_ops)
    wspecs = [*wspecs, *e_specs]
    return pl.pallas_call(
        functools.partial(kernel, geom=tap_geom(g), ep=ep),
        grid=grid,
        in_specs=[conv_in_spec(xt), *wspecs],
        out_specs=out_spec,
        out_shape=jax.ShapeDtypeStruct((n, g["ho"], g["wo"], f), out_dtype),
        scratch_shapes=([pltpu.VMEM((g["bh"] * g["bw"], bf), acc_dtype)]
                        if accumulate else []),
        interpret=core.resolve_interpret(interpret),
        name=kernel_name(name, kh, kw, residual),
    )(xt, *operands)


def vdbb_im2col_conv_tc(
    x: jax.Array,
    values: jax.Array,
    indices: jax.Array,
    fmt: DBBFormat,
    kh: int,
    kw: int,
    *,
    scales: jax.Array | None = None,
    bias: jax.Array | None = None,
    relu: bool = False,
    out_scale=None,
    residual: jax.Array | None = None,
    residual_scale=None,
    stride=1,
    padding="SAME",
    bf: int | None = None,
    tile_h: int | None = None,
    tile_w: int | None = None,
    out_dtype=None,
    interpret: bool | None = None,
) -> jax.Array:
    """Fused sparse conv, group-shared patterns. x: (N, H, W, C);
    values: (nb, nnz, F); indices: (nb, nnz) with nb = kh·kw·C/bz.
    int8 operands accumulate in exact int32; ``scales`` (F,) / ``bias``
    (F,) / ``relu`` / ``out_scale`` fuse the layer epilogue into the
    accumulator flush (DESIGN.md §9; out int8 when requantizing), and
    ``residual`` (int8, shaped like the output) · ``residual_scale`` adds
    a residual block's shortcut there, after the bias and before the ReLU."""
    nb, nnz, f = values.shape
    c = nb * fmt.bz // (kh * kw)
    cb = c // fmt.bz
    bf = (core.default_bf(f, *weight_block(values, fmt)) if bf is None
          else core.resolve_tile(f, bf, "bf"))
    v = values.reshape(kh * kw, cb * nnz, f)
    # per tap, the channel of the (·, C) patch each compressed column reads
    pos = core.mux_positions(indices, cb, fmt.bz).reshape(kh * kw, cb * nnz, 1)
    wspecs = [
        pl.BlockSpec((kh * kw, cb * nnz, bf), lambda p, j: (0, 0, j)),
        pl.BlockSpec((kh * kw, cb * nnz, 1), lambda p, j: (0, 0, 0)),
    ]
    return _launch(
        _vdbb_conv_tc_kernel, "vdbb_im2col_conv_tc", x, (v, pos), wspecs, kh, kw,
        stride=stride, padding=padding, bf=bf, tile_h=tile_h, tile_w=tile_w,
        out_dtype=out_dtype, interpret=interpret, scales=scales, bias=bias,
        relu=relu, out_scale=out_scale, residual=residual,
        residual_scale=residual_scale,
    )


def vdbb_im2col_conv_bw(
    x: jax.Array,
    values: jax.Array,
    indices: jax.Array,
    fmt: DBBFormat,
    kh: int,
    kw: int,
    *,
    scales: jax.Array | None = None,
    bias: jax.Array | None = None,
    relu: bool = False,
    out_scale=None,
    residual: jax.Array | None = None,
    residual_scale=None,
    stride=1,
    padding="SAME",
    bf: int | None = None,
    tile_h: int | None = None,
    tile_w: int | None = None,
    out_dtype=None,
    interpret: bool | None = None,
) -> jax.Array:
    """Fused sparse conv, per-column patterns. values/indices: (nb, nnz, F).
    int8 + epilogue as in :func:`vdbb_im2col_conv_tc`."""
    nb, nnz, f = values.shape
    c = nb * fmt.bz // (kh * kw)
    cb = c // fmt.bz
    bf = (core.default_bf(f, *weight_block(values, fmt)) if bf is None
          else core.resolve_tile(f, bf, "bf"))
    # (kh·kw, nnz, cb, F): per tap, slot-major blocks (dbb_expand_block)
    v = values.reshape(kh * kw, cb, nnz, f).transpose(0, 2, 1, 3)
    idx = indices.astype(jnp.int32).reshape(kh * kw, cb, nnz, f).transpose(0, 2, 1, 3)
    spec = pl.BlockSpec((kh * kw, nnz, cb, bf), lambda p, j: (0, 0, 0, j))
    return _launch(
        functools.partial(_vdbb_conv_bw_kernel, bz=fmt.bz), "vdbb_im2col_conv_bw",
        x, (v, idx),
        [spec, spec], kh, kw,
        stride=stride, padding=padding, bf=bf, tile_h=tile_h, tile_w=tile_w,
        out_dtype=out_dtype, interpret=interpret, accumulate=True,
        scales=scales, bias=bias,
        relu=relu, out_scale=out_scale, residual=residual,
        residual_scale=residual_scale,
    )


def vdbb_im2col_conv(
    x: jax.Array,
    dw: DBBWeight,
    kh: int,
    kw: int,
    **kw_args,
) -> jax.Array:
    """Fused sparse conv over a compressed DBBWeight; dispatches tc vs bw
    on the weight's pattern-sharing mode (like ``ops.vdbb_matmul``)."""
    c, f, cb = _conv_weight_geometry(dw, kh, kw)
    if x.shape[-1] != c:
        raise ValueError(f"x has C={x.shape[-1]} but weight encodes C={c}")
    g = dw.fmt.group_size(f)
    if g == f:
        return vdbb_im2col_conv_tc(
            x, dw.values, dw.indices[:, :, 0], dw.fmt, kh, kw, **kw_args
        )
    idx = jnp.repeat(dw.indices, g, axis=2) if g > 1 else dw.indices
    return vdbb_im2col_conv_bw(x, dw.values, idx, dw.fmt, kh, kw, **kw_args)
