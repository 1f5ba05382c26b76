"""Shared kernel-core for every Pallas kernel in this package.

All kernels in this repo are instances of one scheme — the systolic
array's *output-stationary* dataflow (DESIGN.md §2, §6):

* an accumulator tile (fp32, or exact int32 on the int8 operand path —
  DESIGN.md §8) lives in VMEM scratch for the lifetime of one output tile;
* the reduction (K) dimension is the *innermost* grid axis, so the
  accumulator is initialized on the first K step and flushed to the
  output ref on the last;
* every other grid axis picks an output tile.

This module owns that plumbing once: the init/accumulate/store pattern
(:func:`os_accumulate`), the fused flush epilogue (:class:`Epilogue` /
:func:`epilogue_plan` / :func:`split_epilogue` — dequant scale, bias, a
residual block's shortcut, ReLU, requantize-to-int8, all executed once
where the hardware's requantizer sits, DESIGN.md §9), K-innermost grid
construction and the fp32 VMEM scratch + output BlockSpec boilerplate (:func:`os_matmul_call`), tile-size
resolution (:func:`resolve_tile` strict / :func:`pick_tile` permissive /
:func:`pick_tile_padded` aligned defaults), the tile rule every launch
takes its tiles from (:func:`default_matmul_tiles` /
:func:`default_conv_tiles`), the activation mux
(:func:`dbb_mux`), the conv tap loads (:func:`conv_tap`), and
interpret-mode dispatch (:func:`default_interpret`).

Interpret mode (CPU) accepts any block shape; the TPU compiler does not.
The rules it enforces, and that the default tiles here follow:

* a block's last dim is a multiple of 128 lanes, or the whole array dim;
* its second-to-last dim is a multiple of 8 sublanes, or the whole dim
  (int8 operand tiles are padded to 32-row packs, :func:`sublanes`);
* no ``dynamic_slice`` of a loaded value and no reshape that splits the
  lane axis (e.g. ``(bm, kb·bz) → (bm, kb, bz)``) inside a kernel;
* no strided ref load of 8-bit data (stride-2 convs are phase-split in
  the wrapper instead, :func:`phase_split`).

``tests/test_tpu_compile.py`` compiles the serving-path kernels for a
described v5e chip, so a body or tiling that breaks these rules fails on
the CPU, without the chip.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Sequence

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

QMAX = 127  # symmetric int8 clip range for the requantize epilogue
            # (mirrors repro.core.quant.QMAX; kernels.core deliberately
            # keeps zero repro-internal imports)


def default_interpret() -> bool:
    """Interpret mode (the kernel bodies run in Python, any block shape
    accepted) unless the default backend is a TPU."""
    return jax.default_backend() != "tpu"


def resolve_interpret(interpret: bool | None) -> bool:
    return default_interpret() if interpret is None else bool(interpret)


def resolve_tile(dim: int, tile: int, name: str = "tile") -> int:
    """Clamp a requested tile size to the dimension and check divisibility."""
    t = min(tile, dim)
    if t <= 0 or dim % t != 0:
        raise ValueError(f"{name}={tile} does not tile dimension {dim}")
    return t


def pick_tile(dim: int, tile: int) -> int:
    """Largest divisor of ``dim`` that is <= ``tile`` — the permissive
    fallback for *default* tile sizes, so odd CNN shapes (e.g. M = N·Ho·Wo
    not a multiple of 128) work without hand-tuned tiles at every call
    site. Explicit tile requests keep :func:`resolve_tile`'s strict
    divisibility contract.

    When no usable divisor exists near the default (e.g. a prime dim),
    a sub-sublane tile would launch a pathological 1-wide grid; the whole
    dimension becomes one tile instead — correct everywhere, and far
    better than t=1 on real hardware. Dimensions too large for a single
    VMEM tile *and* without divisors still want an explicit tile.
    """
    t = max(1, min(tile, dim))
    while dim % t:
        t -= 1
    if t < 8 <= dim:
        return dim
    return t


def resolve_or_pick(dim: int, tile, default: int, name: str, *, align: int) -> int:
    """``tile`` is None → :func:`aligned_divisor` of the default (the whole
    dim when no ``align`` multiple divides); otherwise the strict
    :func:`resolve_tile` (an explicit request that does not divide is still
    a caller error)."""
    if tile is None:
        return aligned_divisor(dim, default, align)
    return resolve_tile(dim, tile, name)


LANES = 128  # last-dim width of one TPU vector register tile


def sublanes(dtype) -> int:
    """Rows of one native tile for ``dtype``: 8 for 32-bit, 16 for 16-bit
    and 32 for 8-bit operands (narrow types pack along sublanes)."""
    return 8 * 4 // jnp.dtype(dtype).itemsize


def aligned_divisor(dim: int, tile: int, align: int) -> int:
    """Largest multiple of ``align`` that is ≤ ``max(tile, align)`` and
    divides ``dim``; the whole ``dim`` (always a legal block) when none
    does."""
    for t in range(max(tile, align) // align * align, 0, -align):
        if dim % t == 0:
            return t
    return dim


def pick_tile_padded(dim: int, tile: int, align: int) -> tuple:
    """``(t, padded_dim)`` — a default tile the TPU compiler accepts.

    The whole dimension when it fits in ``tile`` (a full-extent block is
    always legal); else the largest multiple of ``align`` in
    ``[tile/2, tile]`` that divides ``dim``; else ``tile`` rounded down to
    ``align`` with the ragged edge zero-padded (the caller pads the
    operand to ``padded_dim`` and slices the result — exact, since padded
    rows/columns are zero). So N=1000 at ``(256, 128)`` pads to 1024
    rather than taking the divisor 250, which is no lane multiple.
    """
    if dim <= tile:
        return dim, dim
    t = max(align, tile // align * align)
    for c in range(t, t // 2 - 1, -align):
        if c > 0 and dim % c == 0:
            return c, dim
    return t, -(-dim // t) * t


def default_kb(nb: int, bz: int) -> int:
    """Default K blocks per grid step: the activation tile (bm, kb·bz) is
    lane-aligned (kb·bz a multiple of 128), or the whole K in one step."""
    align = math.lcm(bz, LANES) // bz
    return aligned_divisor(nb, align, align)


def default_matmul_tiles(m: int, k: int, n: int, bz: int, dtype) -> dict:
    """The ``(bm, bn, kb)`` of one compressed-matmul launch — with
    :func:`default_conv_tiles`, the one tile rule that plans freeze and
    tile-less kernel calls resolve (DESIGN.md §10): bm sublane-aligned
    for the operand dtype, bn lane-aligned (N padded when no aligned
    divisor exists), kb from :func:`default_kb`."""
    return {"bm": pick_tile_padded(m, 128, sublanes(dtype))[0],
            "bn": pick_tile_padded(n, 256, LANES)[0],
            "kb": default_kb(k // bz, bz)}


# VMEM the weight block of a fused conv's default F block may take, double
# buffered by the pipeline (v5e scopes 16 MiB a kernel; ResNet-50's largest,
# s4's 3×3 at 4/8, holds 2304 × 512 int8: 1.2 MB)
CONV_WEIGHT_VMEM = 8 << 20


def default_bf(f: int, k: int, itemsize: int = 1) -> int:
    """The F block of a fused conv whose weight block has ``k`` rows of
    ``itemsize`` bytes a column: all of F. The kernels build an output
    tile's activation patches, and in tc mode its mux (``dbb_mux``), once
    per F block, so a narrower block repeats them and re-streams every
    weight block for every image — a 1×1 conv ran 8–29 % faster on v5e
    with all of F (PERF.md §3), and a k×k conv's mux is as deep as its
    main dot. Only where a double-buffered whole-F weight block would
    overflow :data:`CONV_WEIGHT_VMEM`: the largest lane-aligned divisor of
    F that fits."""
    return aligned_divisor(f, CONV_WEIGHT_VMEM // (2 * k * itemsize), LANES)


def default_conv_tiles(ho: int, wo: int, f: int, k: int, itemsize: int = 1) -> dict:
    """The ``(bf, tile_h, tile_w)`` of one fused-conv launch: the
    :func:`default_bf` block over the whole output map."""
    return {"bf": default_bf(f, k, itemsize), "tile_h": ho, "tile_w": wo}


def pad_tile(dim: int, tile, default: int, align: int = 1) -> tuple:
    """Permissive ops-level tile resolution with a zero-pad escape hatch.

    ``(t, padded_dim)``: None → :func:`pick_tile_padded` of the default;
    an explicit tile is clamped to the dimension, and one that does not
    divide pads the ragged edge instead of raising (so
    :func:`default_matmul_tiles` may pick a lane-aligned N tile that does
    not divide N, as for a 1000-class head). Kernel-level
    wrappers keep :func:`resolve_tile`'s strict contract; only the
    ``ops.*`` entry points pad-and-slice.
    """
    if tile is None:
        return pick_tile_padded(dim, default, align)
    t = max(1, min(int(tile), dim))
    return t, -(-dim // t) * t


def acc_dtype_for(operand_dtype) -> jnp.dtype:
    """Accumulator dtype for an operand dtype: exact int32 for integer
    (int8) operands, fp32 otherwise — the two accumulators the hardware
    datapath has (DESIGN.md §8)."""
    if jnp.issubdtype(operand_dtype, jnp.integer):
        return jnp.dtype(jnp.int32)
    return jnp.dtype(jnp.float32)


@dataclasses.dataclass(frozen=True)
class Epilogue:
    """Static plan of the fused accumulator-flush epilogue (DESIGN.md §9).

    Flags name which fused operands ride after the compute operands — in
    (scale, bias, residual, residual_scale, out_scale) order: each row a
    (1, N) fp32 operand, the residual an int8 tile shaped like the output
    tile — plus the static ReLU flag. Built host-side by
    :func:`epilogue_plan`, consumed kernel-side by :func:`split_epilogue`;
    hashable, so it threads into kernels via ``functools.partial``.
    """

    has_scale: bool = False
    has_bias: bool = False
    relu: bool = False
    has_out_scale: bool = False
    has_residual: bool = False

    @property
    def n_operands(self) -> int:
        return (int(self.has_scale) + int(self.has_bias)
                + 2 * int(self.has_residual) + int(self.has_out_scale))


def epilogue_plan(n: int, bn: int, *, scales=None, bias=None, relu=False,
                  out_scale=None, acc_dtype, in_dtype, out_dtype=None,
                  residual=None, residual_scale=None, residual_spec=None):
    """Resolve the fused-epilogue request into kernel-launch pieces.

    Returns ``(ep, operands, specs, out_dtype)``: the static
    :class:`Epilogue` (None when nothing was requested), the (1, n) fp32
    operand rows (a scalar ``out_scale`` or ``residual_scale`` broadcasts
    across N) with their (1, bn) BlockSpecs indexed on the N grid axis,
    and the resolved output dtype — int8 when requantizing, fp32 when
    scale/bias/ReLU touch the accumulator, else the raw accumulator dtype
    (the pre-epilogue default).

    ``residual`` (int8 codes shaped like the output, read through
    ``residual_spec``, the output's BlockSpec) with ``residual_scale`` adds
    a shortcut branch after the bias and before the ReLU: a bottleneck
    block's closing conv adds its shortcut in the flush.
    """
    if (residual is None) != (residual_scale is None):
        raise ValueError("a residual needs its scale, and a scale its residual")
    ep = Epilogue(scales is not None, bias is not None, bool(relu),
                  out_scale is not None, residual is not None)
    operands, specs = [], []
    spec = pl.BlockSpec((1, bn), lambda *g: (0, g[1]))  # N is grid axis 1

    def row(v):
        r = jnp.asarray(v, jnp.float32).reshape(1, -1)
        operands.append(jnp.broadcast_to(r, (1, n)))
        specs.append(spec)

    for v, present in ((scales, ep.has_scale), (bias, ep.has_bias)):
        if present:
            row(v)
    if ep.has_residual:
        operands.append(residual)
        specs.append(residual_spec)
        row(residual_scale)
    if ep.has_out_scale:
        row(out_scale)
    if out_dtype is None:
        if ep.has_out_scale:
            out_dtype = jnp.int8
        elif ep.has_scale or ep.has_bias or ep.has_residual:
            out_dtype = jnp.float32  # dequant/bias move the tile to fp32
        elif acc_dtype == jnp.dtype(jnp.int32):
            out_dtype = jnp.int32  # raw (or relu-only) int32 stays exact
        else:
            out_dtype = in_dtype
    if not (ep.n_operands or ep.relu):
        ep = None
    return ep, operands, specs, out_dtype


def split_epilogue(ep: Epilogue | None, rest):
    """Split a kernel's trailing refs into flush kwargs + (o_ref, acc_ref).

    ``rest`` is ``[*epilogue_refs, o_ref, acc_ref]`` with the epilogue
    refs in (scale, bias, residual, residual_scale, out_scale) order,
    exactly as :func:`epilogue_plan` appended them. Returns ``(flush,
    o_ref, acc_ref)`` where ``flush`` feeds straight into
    ``os_accumulate(..., **flush)``.
    """
    n = ep.n_operands if ep is not None else 0
    refs = list(rest[:n])
    o_ref, acc_ref = rest[n], rest[n + 1]
    flush = dict(
        scale=refs.pop(0)[...] if ep is not None and ep.has_scale else None,
        bias=refs.pop(0)[...] if ep is not None and ep.has_bias else None,
        relu=ep is not None and ep.relu,
    )
    if ep is not None and ep.has_residual:
        flush["residual"] = refs.pop(0)[...]
        flush["residual_scale"] = refs.pop(0)[...]
    flush["out_scale"] = (
        refs.pop(0)[...] if ep is not None and ep.has_out_scale else None
    )
    return flush, o_ref, acc_ref


def os_accumulate(acc_ref, o_ref, contribution, *, grid_axis: int, scale=None,
                  bias=None, relu: bool = False, out_scale=None, residual=None,
                  residual_scale=None):
    """Output-stationary accumulation step.

    Zeroes ``acc_ref`` on the first step of the reduction grid axis
    (``grid_axis``, the innermost one), adds ``contribution`` (fp32 or
    int32, matching the scratch), and flushes to ``o_ref`` on the last
    step. ``contribution`` must have ``acc_ref``'s shape; ``o_ref`` may
    have a different (same-size) shape — e.g. a conv output tile with
    leading batch dim — and the accumulator is reshaped on store.

    The optional epilogue (DESIGN.md §9) runs once on the flush, in
    dataflow order — exactly where the hardware's requantizer sits:

    * ``scale`` (fp32, broadcastable, e.g. a (1, bn) per-output-column
      row): dequantization — the int32 accumulator becomes fp32 · scale.
    * ``bias`` (fp32 row): per-output-channel bias add.
    * ``residual`` (int8 codes shaped like ``o_ref``) · ``residual_scale``
      (fp32 row): the shortcut branch of a residual block, dequantized and
      added.
    * ``relu`` (static): clamp at zero.
    * ``out_scale`` (fp32 row): requantize-to-int8 — the next layer's
      activation scale; the store clips round(acc / out_scale) into
      ±QMAX so inter-layer activations stay int8-resident.
    """

    @pl.when(pl.program_id(grid_axis) == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += contribution

    @pl.when(pl.program_id(grid_axis) == pl.num_programs(grid_axis) - 1)
    def _store():
        store_epilogue(acc_ref[...], o_ref, scale=scale, bias=bias,
                       relu=relu, out_scale=out_scale, residual=residual,
                       residual_scale=residual_scale)


def store_epilogue(acc, o_ref, *, scale=None, bias=None, relu: bool = False,
                   out_scale=None, residual=None, residual_scale=None):
    """The accumulator flush: apply the fused epilogue (see
    :func:`os_accumulate`) to ``acc`` and store it into ``o_ref``. The
    residual is added in the output tile's shape, so its int8 codes are
    never reshaped."""
    if scale is not None:
        acc = acc.astype(jnp.float32) * scale
    if bias is not None:
        acc = acc.astype(jnp.float32) + bias
    if residual is not None:
        acc = (acc.astype(jnp.float32).reshape(residual.shape)
               + residual.astype(jnp.float32) * residual_scale)
    if relu:
        acc = jnp.maximum(acc, jnp.zeros((), acc.dtype))
    if out_scale is not None:
        acc = jnp.clip(jnp.round(acc.astype(jnp.float32) / out_scale),
                       -QMAX, QMAX)
    o_ref[...] = acc.reshape(o_ref.shape).astype(o_ref.dtype)


# ---------------------------------------------------------------------------
# Conv geometry (shared by the dense and VDBB fused im2col conv kernels)
# ---------------------------------------------------------------------------


def _pair(v):
    if isinstance(v, int):
        return (v, v)
    a, b = v
    return (int(a), int(b))


def conv_geometry(h: int, w: int, kh: int, kw: int, stride, padding):
    """Resolve stride / padding / output size for a 2-D conv.

    ``stride``: int or (sh, sw). ``padding``: 'SAME' | 'VALID' |
    ((top, bottom), (left, right)). Returns
    ``((sh, sw), ((pt, pb), (pl, pr)), (ho, wo))`` with XLA's SAME
    convention (extra padding goes at the end).
    """
    sh, sw = _pair(stride)

    def one(dim, k, s, pad):
        if pad == "SAME":
            o = -(-dim // s)
            total = max((o - 1) * s + k - dim, 0)
            return (total // 2, total - total // 2), o
        if pad == "VALID":
            if dim < k:
                raise ValueError(f"VALID conv: dim {dim} < kernel {k}")
            return (0, 0), (dim - k) // s + 1
        lo, hi = pad
        return (int(lo), int(hi)), (dim + lo + hi - k) // s + 1

    if isinstance(padding, str):
        padding = padding.upper()
        if padding not in ("SAME", "VALID"):
            raise ValueError(f"padding must be 'SAME', 'VALID', or explicit pairs; got {padding!r}")
        (ph, ho), (pw, wo) = one(h, kh, sh, padding), one(w, kw, sw, padding)
    else:
        (ph, ho), (pw, wo) = one(h, kh, sh, padding[0]), one(w, kw, sw, padding[1])
    if ho < 1 or wo < 1:
        raise ValueError(f"empty conv output {(ho, wo)}")
    return (sh, sw), (ph, pw), (ho, wo)


def extract_conv_tiles(xp: jax.Array, *, bh, bw, sh, sw, kh, kw, th, tw):
    """Gather overlapping spatial input tiles (with halo) for a tiled conv.

    ``xp``: padded (N, Hp, Wp, C). Each output tile is bh×bw output pixels;
    its input footprint is ``bh_in × bw_in = ((bh-1)sh+kh) × ((bw-1)sw+kw)``.
    Returns ``(N·th·tw, bh_in, bw_in, C)``. Only the halo (kh-sh rows /
    kw-sw cols per tile seam) is duplicated in HBM — the raw activation
    tile is still read ~once, unlike the kh·kw× blow-up of explicit im2col.
    """
    n, hp, wp, c = xp.shape
    bh_in = (bh - 1) * sh + kh
    bw_in = (bw - 1) * sw + kw
    if th == 1 and tw == 1:
        return xp
    rows = (jnp.arange(th) * (bh * sh))[:, None] + jnp.arange(bh_in)[None]
    cols = (jnp.arange(tw) * (bw * sw))[:, None] + jnp.arange(bw_in)[None]
    t = jnp.take(xp, rows.reshape(-1), axis=1).reshape(n, th, bh_in, wp, c)
    t = jnp.take(t, cols.reshape(-1), axis=3).reshape(n, th, bh_in, tw, bw_in, c)
    return t.transpose(0, 1, 3, 2, 4, 5).reshape(n * th * tw, bh_in, bw_in, c)


def phase_split(tiles: jax.Array, sh: int, sw: int) -> jax.Array:
    """``(T, bh_in, bw_in, C)`` input tiles → ``(T, sh·sw, Hq, Wq, C)``
    stride phases: phase ``(p, q)`` holds rows ``p::sh`` and columns
    ``q::sw``. Every tap of a strided conv is then a *contiguous* window
    of one phase (:func:`conv_tap`), so the kernel needs no strided load,
    which the TPU compiler refuses for 8-bit data. Stride 1 is one phase
    (a free reshape)."""
    t, h, w, c = tiles.shape
    hq, wq = -(-h // sh), -(-w // sw)
    if sh == sw == 1:
        return tiles.reshape(t, 1, h, w, c)
    x = jnp.pad(tiles, ((0, 0), (0, hq * sh - h), (0, wq * sw - w), (0, 0)))
    x = x.reshape(t, hq, sh, wq, sw, c).transpose(0, 2, 4, 1, 3, 5)
    return x.reshape(t, sh * sw, hq, wq, c)


def conv_tap(x_ref, dy: int, dx: int, *, bh, bw, sh, sw):
    """The (bh·bw, C) activation matrix of kernel tap ``(dy, dx)`` — the
    IM2COL unit. ``x_ref`` is the phase-split VMEM input tile
    ``(1, sh·sw, Hq, Wq, C)`` (:func:`phase_split`); the tap is one static
    contiguous window of one phase, loaded straight from the ref, so the
    kh·kw-duplicated im2col tensor is never materialized."""
    p = (dy % sh) * sw + dx % sw
    patch = x_ref[0, p, pl.ds(dy // sh, bh), pl.ds(dx // sw, bw), :]
    return patch.reshape(bh * bw, patch.shape[-1])


def mux_positions(indices: jax.Array, kb: int, bz: int) -> jax.Array:
    """``(nb, nnz)`` intra-block positions → ``(nb·nnz, 1)`` int32: the
    row of its K tile (``kb`` blocks of ``bz``) that each compressed-K
    column reads — the operand :func:`dbb_mux` selects with."""
    nb, _ = indices.shape
    blk = (jnp.arange(nb, dtype=jnp.int32) % kb) * bz
    return (blk[:, None] + indices.astype(jnp.int32)).reshape(-1, 1)


def dbb_mux(a: jax.Array, pos: jax.Array) -> jax.Array:
    """The activation mux of the paper's S8DP1 lane as one 2-D selection
    matmul: ``a`` (m, kt) × one-hot ``[iota == pos]`` (c, kt), contracted
    on kt, gives the (m, c) compressed-K tile ``a[:, pos[j]]``.

    Exact: every output is one operand value times 1 (int8 accumulates in
    int32, fp32 at full precision), cast back to the operand dtype. No
    lane axis is split, which is what lets the TPU compiler take it."""
    c, kt = pos.shape[0], a.shape[1]
    sel = (jax.lax.broadcasted_iota(jnp.int32, (c, kt), 1) == pos).astype(a.dtype)
    return mxu_dot(a, sel, contract_rhs=1).astype(a.dtype)


def mxu_dot(a: jax.Array, b: jax.Array, *, contract_rhs: int = 0) -> jax.Array:
    """``a @ b`` (or ``a @ b.T`` with ``contract_rhs=1``) into the
    datapath's accumulator: exact int32 for int8 operands, fp32 for float
    ones. fp32 operands multiply at full fp32 precision, as in interpret
    mode — the TPU's default would round them to bf16 first."""
    f32 = jnp.dtype(a.dtype) == jnp.float32
    return jax.lax.dot_general(
        a, b.astype(a.dtype), (((1,), (contract_rhs,)), ((), ())),
        preferred_element_type=acc_dtype_for(a.dtype),
        precision=jax.lax.Precision.HIGHEST if f32 else None,
    )


def os_matmul_call(
    kernel,
    operands: Sequence[jax.Array],
    *,
    m: int,
    n: int,
    bm: int,
    bn: int,
    k_steps: int,
    in_specs: Sequence[pl.BlockSpec],
    out_dtype,
    name: str,
    acc_dtype=jnp.float32,
    interpret: bool | None = None,
):
    """Launch an output-stationary (M, N) matmul-shaped kernel named
    ``name`` (the kernel's name in a device profile).

    Builds the K-innermost grid ``(m//bm, n//bn, k_steps)``, the ``(bm, bn)``
    output BlockSpec and the VMEM accumulator scratch (fp32, or int32 for
    the int8 operand path — ``acc_dtype``), and invokes ``pl.pallas_call``.
    The kernel receives ``(*operand_refs, o_ref, acc_ref)`` and is expected
    to compute one K-step contribution and hand it to :func:`os_accumulate`
    with ``grid_axis=2``.
    """
    grid = (m // bm, n // bn, k_steps)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=list(in_specs),
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, s: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), out_dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), acc_dtype)],
        interpret=resolve_interpret(interpret),
        name=name,
    )(*operands)
