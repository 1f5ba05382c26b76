"""Jit'd public wrappers around the Pallas kernels with mode dispatch.

``interpret`` defaults to True unless the default backend is a TPU
(``core.default_interpret``): the same call sites run in interpret mode
on the CPU and compiled on a TPU. The compiled path holds only block
shapes and bodies the TPU compiler accepts (kernels/core.py);
``tests/test_tpu_compile.py`` checks that without the chip.

Dtype dispatch (DESIGN.md §8): the same entry points accept fp32/bf16 or
int8 operands. Integer operands run the int8 datapath — exact int32 OS
accumulation — and return the raw int32 accumulator; the quantized
end-to-end path (`quant_matmul` / `quant_conv`) additionally quantizes the
fp activation per-tensor and fuses the dequantization into the accumulator
flush via the kernels' ``scales`` operand.

Epilogue fusion (DESIGN.md §9): every entry point takes ``bias=``,
``relu=`` and ``out_scale=`` and folds them into the accumulator flush —
one kernel per layer. ``out_scale`` (the *next* layer's activation scale)
requantizes the flush to int8, so inter-layer activations stay
int8-resident; the quantized entry points also accept an **int8** input
(already-quantized codes from the previous layer's epilogue) together
with its ``act_scale``, skipping the per-layer quantize pass entirely.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.core.quant import QuantDBBWeight, resolve_quant_input
from repro.core.vdbb import DBBFormat, DBBWeight
from repro.kernels import core
from repro.kernels import im2col_conv as _im2col
from repro.kernels import vdbb_im2col_conv as _vconv
from repro.kernels import vdbb_matmul as _vm


def _default_interpret() -> bool:
    return core.default_interpret()


def _pad_epilogue_row(v, n, n_pad, fill=0.0):
    """Pad a per-output-column epilogue vector out to the padded N (scalars
    broadcast unchanged; ``fill`` must be non-zero for ``out_scale`` so the
    sliced-away columns never divide by zero)."""
    if v is None:
        return None
    v = jnp.asarray(v, jnp.float32)
    if v.ndim == 0:
        return v
    return jnp.pad(v.reshape(-1), (0, n_pad - n), constant_values=fill)


def _matmul_dispatch(a, w, scales, bm, bn, kb, interpret, *, bias=None,
                     relu=False, out_scale=None):
    """tc vs bw on the weight's pattern-sharing mode (shared by the fp,
    raw-int8 and quantized entry points).

    Tile resolution is permissive here (the ops layer): tiles not given
    come from ``core.default_matmul_tiles``, and ``bm``/``bn`` that do not
    divide M/N take the pad-to-tile path — the ragged edge is zero-padded
    and sliced back off, which is exact (padded rows/columns contribute
    nothing; padded ``out_scale`` columns divide by 1 and are discarded).
    ``kb`` stays an exact divisor of the K-block count. The kernel-level
    wrappers keep the strict divisibility contract.
    """
    m, k = a.shape
    n = w.shape[1]
    fmt = w.fmt
    g = fmt.group_size(n)
    tc = g == n
    if bm is None or bn is None or kb is None:
        rule = core.default_matmul_tiles(m, k, n, fmt.bz, a.dtype)
        bm = rule["bm"] if bm is None else bm
        bn = rule["bn"] if bn is None else bn
        kb = rule["kb"] if kb is None else kb
    bm, mp = core.pad_tile(m, bm, 128, core.sublanes(a.dtype))
    bn, n_pad = core.pad_tile(n, bn, 256, core.LANES)
    if mp != m:
        a = jnp.pad(a, ((0, mp - m), (0, 0)))
    values = w.values
    if tc:
        idx = w.indices[:, :, 0]
    elif g != 1:
        # grouped-but-not-matrix: expand indices per column, use bw kernel.
        idx = jnp.repeat(w.indices, g, axis=2)
    else:
        idx = w.indices
    if n_pad != n:
        values = jnp.pad(values, ((0, 0), (0, 0), (0, n_pad - n)))
        if not tc:
            idx = jnp.pad(idx, ((0, 0), (0, 0), (0, n_pad - n)))
        scales = _pad_epilogue_row(scales, n, n_pad)
        bias = _pad_epilogue_row(bias, n, n_pad)
        out_scale = _pad_epilogue_row(out_scale, n, n_pad, fill=1.0)
    kw = dict(scales=scales, bias=bias, relu=relu, out_scale=out_scale,
              bm=bm, bn=bn, kb=kb, interpret=interpret)
    fn = _vm.vdbb_matmul_tc if tc else _vm.vdbb_matmul_bw
    y = fn(a, values, idx, fmt, **kw)
    if mp != m or n_pad != n:
        y = y[:m, :n]
    return y


@functools.partial(jax.jit, static_argnames=("relu", "bm", "bn", "kb", "interpret"))
def vdbb_matmul(
    a: jax.Array,
    w: DBBWeight,
    *,
    bias: jax.Array | None = None,
    relu: bool = False,
    out_scale=None,
    bm: int | None = None,
    bn: int | None = None,
    kb: int | None = None,
    interpret: bool | None = None,
) -> jax.Array:
    """A (M, K) @ compressed DBB W (K, N) -> (M, N). Dispatches tc vs bw on
    the weight's pattern-sharing mode, and on operand dtype: int8 operands
    run the int32-accumulator datapath and return the raw int32
    accumulator (quantized end-to-end: :func:`quant_matmul`). ``bias`` /
    ``relu`` / ``out_scale`` fuse the fp epilogue into the flush
    (DESIGN.md §9; int8 out when requantizing)."""
    interpret = _default_interpret() if interpret is None else interpret
    return _matmul_dispatch(a, w, None, bm, bn, kb, interpret, bias=bias,
                            relu=relu, out_scale=out_scale)


@functools.partial(jax.jit, static_argnames=("relu", "bm", "bn", "kb", "interpret"))
def quant_matmul(
    x: jax.Array,
    qw: QuantDBBWeight,
    act_scale: jax.Array | None = None,
    *,
    bias: jax.Array | None = None,
    relu: bool = False,
    out_scale=None,
    bm: int | None = None,
    bn: int | None = None,
    kb: int | None = None,
    interpret: bool | None = None,
) -> jax.Array:
    """X (M, K) × int8-quantized compressed W -> fp32 (M, N), or int8 when
    ``out_scale`` is given.

    ``x`` may be fp (quantized per-tensor with ``act_scale`` from
    calibration, or dynamically when None) or already int8 (the previous
    layer's requantized codes; ``act_scale`` then required). The whole
    epilogue — dequant (``act_scale · w_scale[n]``), ``bias``, ``relu``,
    requantize at ``out_scale`` — runs fused on the accumulator flush
    (DESIGN.md §9), so one call is one kernel with zero standalone fp32
    passes.
    """
    interpret = _default_interpret() if interpret is None else interpret
    xq, s_a = resolve_quant_input(x, act_scale)
    scales = s_a * qw.scales
    return _matmul_dispatch(xq, qw.as_dbb(), scales, bm, bn, kb, interpret,
                            bias=bias, relu=relu, out_scale=out_scale)


def sparse_matmul(
    a: jax.Array,
    w: DBBWeight,
    *,
    act_fmt: DBBFormat | None = None,
    **kw,
) -> jax.Array:
    """:func:`vdbb_matmul` with optional structural activation gating.

    ``act_fmt`` (DESIGN.md §7) projects the activations onto the
    block-wise top-|x| DBB constraint (pattern shared across the M tile)
    before the kernel — the activation-side twin of the weight format,
    typically ``act_fmt(measure_activation(a))`` from
    :mod:`repro.core.act_sparsity`. Pruned activations flow through the
    tc kernel's compressed-K contraction unchanged.
    """
    if act_fmt is not None:
        from repro.core.act_sparsity import act_dbb_prune

        a = act_dbb_prune(a, act_fmt)
    return vdbb_matmul(a, w, **kw)


@functools.partial(
    jax.jit,
    static_argnames=("relu", "stride", "padding", "bf", "tile_h", "tile_w", "interpret"),
)
def fused_im2col_conv(
    x: jax.Array,
    w: jax.Array,
    *,
    bias: jax.Array | None = None,
    relu: bool = False,
    out_scale=None,
    stride=1,
    padding="SAME",
    bf: int | None = None,
    tile_h: int | None = None,
    tile_w: int | None = None,
    interpret: bool | None = None,
) -> jax.Array:
    """Fused im2col+GEMM conv (NHWC / HWIO), dense weights. ``bias`` /
    ``relu`` / ``out_scale`` fuse the layer epilogue into the flush
    (DESIGN.md §9) — with ``out_scale`` the fp32 stem of an int8-resident
    model emits int8 directly."""
    interpret = _default_interpret() if interpret is None else interpret
    return _im2col.im2col_conv(
        x, w, bias=bias, relu=relu, out_scale=out_scale, stride=stride,
        padding=padding, bf=bf, tile_h=tile_h, tile_w=tile_w, interpret=interpret,
    )


@functools.partial(
    jax.jit,
    static_argnames=("kh", "kw", "relu", "stride", "padding", "bf", "tile_h", "tile_w", "interpret"),
)
def sparse_conv(
    x: jax.Array,
    w: DBBWeight,
    kh: int,
    kw: int,
    *,
    bias: jax.Array | None = None,
    relu: bool = False,
    out_scale=None,
    stride=1,
    padding="SAME",
    bf: int | None = None,
    tile_h: int | None = None,
    tile_w: int | None = None,
    interpret: bool | None = None,
) -> jax.Array:
    """Fused IM2COL × VDBB sparse conv over a compressed DBB conv weight
    (K = kh·kw·C along the reduction). Dispatches tc vs bw on the weight's
    pattern-sharing mode — the paper's full datapath in one call. int8
    operands return the raw int32 accumulator (quantized end-to-end:
    :func:`quant_conv`); ``bias`` / ``relu`` / ``out_scale`` fuse the fp
    epilogue into the flush (DESIGN.md §9; int8 out when requantizing)."""
    interpret = _default_interpret() if interpret is None else interpret
    return _vconv.vdbb_im2col_conv(
        x, w, kh, kw, bias=bias, relu=relu, out_scale=out_scale,
        stride=stride, padding=padding, bf=bf, tile_h=tile_h, tile_w=tile_w,
        interpret=interpret,
    )


@functools.partial(
    jax.jit,
    static_argnames=("kh", "kw", "relu", "stride", "padding", "bf", "tile_h", "tile_w", "interpret"),
)
def quant_conv(
    x: jax.Array,
    qw: QuantDBBWeight,
    kh: int,
    kw: int,
    act_scale: jax.Array | None = None,
    *,
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
    interpret: bool | None = None,
) -> jax.Array:
    """NHWC × int8-quantized compressed conv weight -> fp32 NHWC, or int8
    NHWC when ``out_scale`` is given.

    The conv twin of :func:`quant_matmul`: fp input is quantized
    per-tensor (calibrated ``act_scale`` or dynamic); int8 input is the
    previous layer's requantized codes (int8-resident chaining, zero-
    padding is exact under the symmetric scheme). Dequantization, bias,
    ReLU and the requantize at ``out_scale`` all fuse into the
    accumulator flush — one kernel per conv layer (DESIGN.md §9).
    ``residual`` (int8 codes shaped like the output) at ``residual_scale``
    is a residual block's shortcut, added after the bias, before the ReLU.
    """
    interpret = _default_interpret() if interpret is None else interpret
    xq, s_a = resolve_quant_input(x, act_scale)
    return _vconv.vdbb_im2col_conv(
        xq, qw.as_dbb(), kh, kw, scales=s_a * qw.scales, bias=bias, relu=relu,
        out_scale=out_scale, residual=residual, residual_scale=residual_scale,
        stride=stride, padding=padding, bf=bf,
        tile_h=tile_h, tile_w=tile_w, interpret=interpret,
    )

