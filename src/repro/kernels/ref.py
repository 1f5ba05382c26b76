"""Pure-jnp oracles for every kernel in this package."""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels.core import _pair
from repro.core.vdbb import (  # noqa: F401  (re-exported oracles)
    DBBFormat,
    DBBWeight,
    dbb_decode,
    dbb_decode_conv,
    dbb_encode_conv,
    dbb_matmul_gather_ref,
    dbb_matmul_ref,
)


def vdbb_matmul_ref(a: jax.Array, values: jax.Array, indices: jax.Array, fmt: DBBFormat):
    """Oracle shared by tc and bw kernels: expand-to-dense then matmul.

    values: (nb, nnz, N); indices: (nb, nnz) [tc, shared pattern] or
    (nb, nnz, N) [bw, per-column].
    """
    import dataclasses

    nb, nnz, n = values.shape
    if indices.ndim == 2:
        indices = jnp.broadcast_to(indices[:, :, None], (nb, nnz, n))
    # decode with per-column semantics regardless of the sharing mode the
    # kernel used (shared patterns are just repeated columns).
    fmt_pc = dataclasses.replace(fmt, group=None)
    dw = DBBWeight(values, indices.astype(jnp.int8), fmt_pc, (nb * fmt.bz, n))
    return jnp.matmul(a, dbb_decode(dw).astype(a.dtype))


def vdbb_matmul_int_ref(a: jax.Array, values: jax.Array, indices: jax.Array,
                        fmt: DBBFormat) -> jax.Array:
    """Integer oracle for the int8 tc/bw kernels: expand the int8 compressed
    weight to dense and accumulate in exact int32 — the raw OS accumulator
    the hardware produces before requantization (DESIGN.md §8).

    a: (M, K) int8; values: (nb, nnz, N) int8; indices as in
    :func:`vdbb_matmul_ref`. Returns (M, N) int32, bit-exact.
    """
    import dataclasses

    nb, nnz, n = values.shape
    if indices.ndim == 2:
        indices = jnp.broadcast_to(indices[:, :, None], (nb, nnz, n))
    fmt_pc = dataclasses.replace(fmt, group=None)
    dw = DBBWeight(values, indices.astype(jnp.int8), fmt_pc, (nb * fmt.bz, n))
    return jnp.matmul(a.astype(jnp.int32), dbb_decode(dw).astype(jnp.int32))


def quant_epilogue_ref(acc: jax.Array, scale, *, bias=None, relu=False,
                       out_scale=None, residual=None,
                       residual_scale=None) -> jax.Array:
    """Integer-oracle layer epilogue (DESIGN.md §9): the exact fp32 ops the
    kernels fuse into the accumulator flush, in dataflow order —
    dequantize → bias → + residual → ReLU → requantize-to-int8.

    ``acc``: raw int32 OS accumulator (last axis = output channels);
    ``scale``: fused dequant ``act_scale · w_scale[n]``, broadcast on the
    last axis; ``out_scale``: the next layer's activation scale — when
    given the result is int8 codes in ±127, bit-exact against the fused
    kernels. Without it the fp32 epilogue output is returned.
    ``residual``: int8 codes shaped like ``acc`` (a residual block's
    shortcut), dequantized at ``residual_scale`` and added.
    """
    y = acc.astype(jnp.float32) * scale
    if bias is not None:
        y = y + bias.astype(jnp.float32)
    if residual is not None:
        y = y + residual.astype(jnp.float32) * residual_scale
    if relu:
        y = jnp.maximum(y, 0.0)
    if out_scale is not None:
        # ±127 == quant.QMAX == kernels.core.QMAX (the symmetric int8 range)
        return jnp.clip(jnp.round(y / out_scale), -127, 127).astype(jnp.int8)
    return y


def im2col_explicit(x: jax.Array, kh: int, kw: int, *, stride=1, padding="SAME") -> jax.Array:
    """Explicit im2col producing the duplicated (N, Ho, Wo, kh*kw*C) tensor —
    the memory-footprint blow-up the hardware unit avoids."""
    from repro.kernels.core import conv_geometry

    n, h, w, c = x.shape
    (sh, sw), (ph, pw), (ho, wo) = conv_geometry(h, w, kh, kw, stride, padding)
    xp = jnp.pad(x, ((0, 0), ph, pw, (0, 0)))
    cols = [
        xp[:, dy : dy + (ho - 1) * sh + 1 : sh, dx : dx + (wo - 1) * sw + 1 : sw, :]
        for dy in range(kh)
        for dx in range(kw)
    ]
    return jnp.concatenate(cols, axis=-1)


def im2col_conv_ref(x: jax.Array, w: jax.Array, *, stride=1, padding="SAME") -> jax.Array:
    """Conv as explicit im2col + GEMM (the baseline the kernel beats)."""
    kh, kw, c, f = w.shape
    cols = im2col_explicit(x, kh, kw, stride=stride, padding=padding)
    return jnp.einsum("nhwk,kf->nhwf", cols, w.reshape(kh * kw * c, f)).astype(x.dtype)


def conv_lax_ref(x: jax.Array, w: jax.Array, *, stride=1, padding="SAME") -> jax.Array:
    """XLA native conv oracle (NHWC, HWIO)."""
    return jax.lax.conv_general_dilated(
        x,
        w,
        window_strides=_pair(stride),
        padding=padding,
        dimension_numbers=("NHWC", "HWIO", "NHWC"),
    ).astype(x.dtype)


def sparse_conv_ref(x: jax.Array, dw: DBBWeight, kh: int, kw: int, *, stride=1,
                    padding="SAME") -> jax.Array:
    """Oracle for the fused IM2COL × VDBB kernel: decode the compressed conv
    weight to dense (kh, kw, C, F) and run the XLA conv."""
    w4 = dbb_decode_conv(dw, kh, kw).astype(x.dtype)
    return conv_lax_ref(x, w4, stride=stride, padding=padding)


def sparse_conv_int_ref(x: jax.Array, dw: DBBWeight, kh: int, kw: int, *,
                        stride=1, padding="SAME") -> jax.Array:
    """Integer oracle for the int8 fused conv kernels: dtype-preserving
    explicit im2col (pad/slice/concat) + exact int32 GEMM over the decoded
    int8 weight. x: (N, H, W, C) int8; returns (N, Ho, Wo, F) int32."""
    cols = im2col_explicit(x, kh, kw, stride=stride, padding=padding)
    n, ho, wo, kk = cols.shape
    w2 = dbb_decode(dw).astype(jnp.int32)  # (K, F)
    acc = jnp.matmul(cols.reshape(-1, kk).astype(jnp.int32), w2)
    return acc.reshape(n, ho, wo, -1)
