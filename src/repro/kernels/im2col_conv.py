"""Fused IM2COL + GEMM Pallas kernel — the paper's "bandwidth magnifier".

The paper's hardware IM2COL unit sits *after* SRAM, expanding the activation
stream 3× right before the datapath so the SRAM never stores or re-reads the
im2col-duplicated pixels. The TPU-native analogue: read the raw (H, W, C)
activation tile from HBM exactly once into VMEM and materialize the im2col
expansion there alone: the kh·kw shifted views of the tile go side by side
along the lanes of one (HW, kh·kw·C) patch, and the conv is one
(HW, kh·kw·C)×(kh·kw·C, F) matmul per chunk of output rows (kernel
``im2col_conv_packed``). A dot per tap would contract only C, and a
contraction far below the MXU's 128 rows still pays a whole pass (six at
fp32 ``HIGHEST``): the C=3 stem's nine taps of K=3 become one dot of K=27.

HBM activation traffic: H·W·C  (vs kh·kw·H·W·C for explicit im2col+GEMM,
i.e. 9× less for 3×3 — the paper reports 3× average SRAM-read reduction for
their 6×2 line buffer; a full-tile VMEM buffer does strictly better).

Layout: NHWC input, HWIO weights. Strides, even kernels, SAME/VALID/
explicit padding and spatial H×W output tiling (bounded VMEM for large
feature maps) are all supported; geometry and the shifted-view tap come
from :mod:`repro.kernels.core` (DESIGN.md §6). The grid is (output tile,
F block); the kh·kw taps are a static loop inside each step, each a
static window of the VMEM input tile. The TPU compiler
takes no ``dynamic_slice`` of a loaded value and no strided load of 8-bit
data, so the tap offsets are static and a strided conv reads a
stride-phase split of its input tile (``core.phase_split``, one XLA pass
in the wrapper) instead of striding.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import core


def plan_conv(x, kh, kw, *, stride, padding, tile_h=None, tile_w=None):
    """Host-side conv planning shared by the dense and VDBB fused kernels.

    Pads ``x`` to the exact input footprint, extracts halo'd spatial tiles
    (no-op when untiled), splits them into stride phases, and returns
    ``(tiles, geom)``: tiles ``(N·th·tw, sh·sw, Hq, Wq, C)`` and geom with
    every static the kernels and BlockSpecs need.
    """
    n, h, w, c = x.shape
    (sh, sw), (ph, pw), (ho, wo) = core.conv_geometry(h, w, kh, kw, stride, padding)
    if kh == kw == 1 and (sh, sw) != (1, 1):
        # a strided 1×1 conv reads one pixel in sh·sw: subsample, then run
        # it at stride 1 (no stride-phase split of the whole input)
        x = jnp.pad(x, ((0, 0), ph, pw, (0, 0)))[:, ::sh, ::sw][:, :ho, :wo]
        n, h, w, c = x.shape
        (sh, sw), (ph, pw) = (1, 1), ((0, 0), (0, 0))
    bh = core.resolve_tile(ho, tile_h or ho, "tile_h")
    bw = core.resolve_tile(wo, tile_w or wo, "tile_w")
    th, tw = ho // bh, wo // bw
    need_h = (ho - 1) * sh + kh
    need_w = (wo - 1) * sw + kw
    xp = jnp.pad(
        x,
        (
            (0, 0),
            (ph[0], max(ph[1], need_h - h - ph[0])),
            (pw[0], max(pw[1], need_w - w - pw[0])),
            (0, 0),
        ),
    )[:, :need_h, :need_w, :]
    xt = core.extract_conv_tiles(xp, bh=bh, bw=bw, sh=sh, sw=sw, kh=kh, kw=kw, th=th, tw=tw)
    xt = core.phase_split(xt, sh, sw)
    geom = dict(
        n=n, c=c, ho=ho, wo=wo, sh=sh, sw=sw, bh=bh, bw=bw, th=th, tw=tw,
        kh=kh, kw=kw,
    )
    return xt, geom


def conv_in_spec(xt):
    """Input BlockSpec: one whole phase-split tile per spatial grid index."""
    return pl.BlockSpec((1, *xt.shape[1:]), lambda p, j: (p, 0, 0, 0, 0))


def conv_out_spec(geom, bf):
    """Output BlockSpec: one (1, bh, bw, bf) tile of the (N, Ho, Wo, F) map."""
    th, tw = geom["th"], geom["tw"]
    return pl.BlockSpec(
        (1, geom["bh"], geom["bw"], bf),
        lambda p, j: (p // (th * tw), (p % (th * tw)) // tw, p % tw, j),
    )


def tap_geom(geom) -> dict:
    """The statics :func:`conv_taps` and :func:`packed_patches` need, from
    a :func:`plan_conv` geom."""
    return {k: geom[k] for k in ("kh", "kw", "sh", "sw", "bh", "bw")}


def conv_taps(x_ref, tap_contribution, acc_ref, *, kh, kw, sh, sw, bh, bw):
    """Accumulate every kernel tap of one output tile into ``acc_ref``:
    ``tap_contribution(t, patch)`` maps tap ``t``'s (bh·bw, C) activation
    matrix (:func:`core.conv_tap`) to its (bh·bw, bf) partial sum. The
    taps are a static loop, so every window offset is a constant."""
    for t in range(kh * kw):
        patch = core.conv_tap(x_ref, t // kw, t % kw, bh=bh, bw=bw, sh=sh, sw=sw)
        contrib = tap_contribution(t, patch)
        if t == 0:
            acc_ref[...] = contrib
        else:
            acc_ref[...] += contrib


# output pixels per packed dot: the tile's rows go in static chunks of about
# this many, whatever K. On v5e one bucket-128 stem call took 2.33–2.52 ms,
# launch included, in chunks of 64 to 1024 pixels (1024 the slowest) and
# 2.67–2.85 ms as one 4096-pixel patch; at K ≥ 1152, chunks of 16–32 pixels
# ran up to a quarter slower than per-tap dots, 256–512 as fast (PERF.md §6).
PACKED_PIXELS = 512


def packed_patches(x_ref, tap, *, kh, kw, sh, sw, bh, bw):
    """The packed im2col patches of one output tile, chunk by chunk: for
    each static chunk of ``rh`` output rows (about :data:`PACKED_PIXELS`
    pixels) yield ``(r, rh, patch)``, where ``patch`` holds the kh·kw taps'
    ``tap(t, window)`` matrices side by side along the lanes; ``window`` is
    tap ``t``'s (rh·bw, C) activation matrix (:func:`core.conv_tap`). One
    tap packs nothing, so a 1×1 conv's whole tile is one chunk."""
    rh = bh if kh * kw == 1 else core.aligned_divisor(bh, PACKED_PIXELS // bw, 1)
    for r in range(0, bh, rh):  # tap row dy + r·sh: phase dy % sh, row dy // sh + r
        yield r, rh, jnp.concatenate(
            [tap(t, core.conv_tap(x_ref, t // kw + r * sh, t % kw, bh=rh, bw=bw,
                                  sh=sh, sw=sw))
             for t in range(kh * kw)], axis=1)


def _im2col_conv_packed_kernel(x_ref, w_ref, *rest, geom, ep=None):
    """Grid: (N·th·tw, F/bf). x: (1, sh·sw, Hq, Wq, C); w: (kh·kw·C, bf).
    The kh·kw taps of ``rh`` output rows sit side by side along the lanes
    of one (rh·bw, kh·kw·C) patch (:func:`packed_patches`), one MXU
    contraction each; no accumulator scratch."""
    flush, o_ref, _ = core.split_epilogue(ep, (*rest, None))
    w = w_ref[...]
    for r, rh, patch in packed_patches(x_ref, lambda t, window: window, **geom):
        core.store_epilogue(core.mxu_dot(patch, w), o_ref.at[:, pl.ds(r, rh)], **flush)


def im2col_conv(
    x: jax.Array,
    w: jax.Array,
    *,
    scales: jax.Array | None = None,
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
    """Fused im2col conv. x: (N, H, W, C); w: (kh, kw, C, F). The optional
    epilogue (``scales``/``bias``/``relu``/``out_scale``, DESIGN.md §9)
    fuses the layer's bias + ReLU + requantize-to-int8 into the flush, so
    even the fp32 stem of an int8-resident model is one kernel."""
    n, c = x.shape[0], x.shape[3]
    kh, kw, wc, f = w.shape
    if wc != c:
        raise ValueError(f"channel mismatch: x has {c}, w has {wc}")
    xt, g = plan_conv(x, kh, kw, stride=stride, padding=padding, tile_h=tile_h, tile_w=tile_w)
    bf = (core.default_bf(f, kh * kw * c, w.dtype.itemsize) if bf is None
          else core.resolve_tile(f, bf, "bf"))
    grid = (n * g["th"] * g["tw"], f // bf)
    acc_dtype = core.acc_dtype_for(x.dtype)  # int32 on the int8 path (§8)
    ep, e_ops, e_specs, out_dtype = core.epilogue_plan(
        f, bf, scales=scales, bias=bias, relu=relu, out_scale=out_scale,
        acc_dtype=acc_dtype, in_dtype=x.dtype,
    )
    return pl.pallas_call(
        functools.partial(_im2col_conv_packed_kernel, geom=tap_geom(g), ep=ep),
        grid=grid,
        in_specs=[
            conv_in_spec(xt),
            # row (dy·kw + dx)·C + c: the patch's lanes
            pl.BlockSpec((kh * kw * c, bf), lambda p, j: (0, j)),
            *e_specs,
        ],
        out_specs=conv_out_spec(g, bf),
        out_shape=jax.ShapeDtypeStruct((n, g["ho"], g["wo"], f), out_dtype),
        interpret=core.resolve_interpret(interpret),
        name="im2col_conv_packed",
    )(xt, w.reshape(kh * kw * c, f), *e_ops)
