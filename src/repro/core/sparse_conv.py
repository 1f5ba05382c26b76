"""DBBConv2d — the paper's technique on its native workload, CNN layers.

Mirrors :class:`repro.core.sparse_linear.DBBLinear` end-to-end:

Training: the dense (kh, kw, C, F) weight is kept *projected* onto the DBB
constraint along K = kh·kw·C (magnitude top-nnz per bz-block) by
``constrain()``, with the same progressive nnz anneal.

Serving: ``compress_params()`` converts to the compressed DBBWeight layout;
the forward pass then runs the fused IM2COL × VDBB conv — Pallas kernel in
``kernel_mode='pallas'`` (kernels/vdbb_im2col_conv), decode + XLA conv as
the reference path — consuming nnz/bz of the dense weight bandwidth while
reading the raw (un-im2col'd) activation tile.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Optional

import jax
import jax.numpy as jnp

from repro.core.quant import (
    QuantDBBWeight,
    dynamic_act_scale,
    quant_conv_ref,
    quantize as quantize_array,
    quantize_dbb,
    resolve_quant_input,
)
from repro.core.sparse_linear import PruneSchedule
from repro.core.vdbb import (
    DBBFormat,
    DBBWeight,
    DENSE,
    dbb_decode_conv,
    dbb_encode_conv,
    dbb_prune,
)
from repro.kernels.core import _pair  # stride/kernel-size normalizer (no cycle:
                                      # kernels.core has no repro-internal imports)


@dataclasses.dataclass(frozen=True)
class DBBConv2d:
    """y = conv2d(x, W) (+ b); x NHWC, W (kh, kw, C, F), DBB along K=kh·kw·C."""

    in_channels: int
    out_channels: int
    kernel_size: Any = 3  # int or (kh, kw)
    stride: Any = 1
    padding: Any = "SAME"
    fmt: DBBFormat = DENSE
    use_bias: bool = False
    dtype: Any = jnp.float32
    kernel_mode: str = "ref"  # 'ref' | 'pallas' (serving path choice)

    def __post_init__(self):
        if not self.fmt.is_dense and self.in_channels % self.fmt.bz != 0:
            raise ValueError(
                f"in_channels={self.in_channels} not divisible by bz="
                f"{self.fmt.bz}: DBB blocks must not straddle kernel taps"
            )

    @property
    def kh(self) -> int:
        return _pair(self.kernel_size)[0]

    @property
    def kw(self) -> int:
        return _pair(self.kernel_size)[1]

    def init(self, key) -> dict:
        kh, kw = self.kh, self.kw
        fan_in = kh * kw * self.in_channels
        scale = 1.0 / (fan_in**0.5)
        w = scale * jax.random.truncated_normal(
            key, -2, 2, (kh, kw, self.in_channels, self.out_channels), self.dtype
        )
        if not self.fmt.is_dense:
            w = self._project(w, self.fmt)
        p = {"w": w}
        if self.use_bias:
            p["b"] = jnp.zeros((self.out_channels,), self.dtype)
        return p

    # ------------------------------------------------------------------
    def _project(self, w4: jax.Array, fmt: DBBFormat) -> jax.Array:
        kh, kw, c, f = w4.shape
        return dbb_prune(w4.reshape(kh * kw * c, f), fmt).reshape(w4.shape)

    def __call__(self, params: dict, x: jax.Array) -> jax.Array:
        w = params["w"]
        if isinstance(w, QuantDBBWeight):
            y = self._quantized_conv(x, w, params.get("aq"))
        elif isinstance(w, DBBWeight):
            y = self._compressed_conv(x, w)
        else:
            y = jax.lax.conv_general_dilated(
                x,
                w.astype(x.dtype),
                window_strides=_pair(self.stride),
                padding=self.padding,
                dimension_numbers=("NHWC", "HWIO", "NHWC"),
                # fp32 at fp32: a TPU's default would round to bf16 first
                precision=jax.lax.Precision.HIGHEST,
            )
        if self.use_bias:
            y = y + params["b"].astype(y.dtype)
        return y

    def _compressed_conv(self, x: jax.Array, w: DBBWeight) -> jax.Array:
        if self.kernel_mode == "pallas":
            from repro.kernels import ops  # deferred: kernels are optional

            return ops.sparse_conv(
                x, w, self.kh, self.kw, stride=_pair(self.stride), padding=self.padding
            )
        w4 = dbb_decode_conv(w, self.kh, self.kw).astype(x.dtype)
        return jax.lax.conv_general_dilated(
            x,
            w4,
            window_strides=_pair(self.stride),
            padding=self.padding,
            dimension_numbers=("NHWC", "HWIO", "NHWC"),
        )

    def _quantized_conv(self, x: jax.Array, qw: QuantDBBWeight, aq) -> jax.Array:
        """INT8 serving conv: per-tensor act quant (calibrated ``aq`` or
        dynamic), int8 fused kernel / integer reference, fp32 out."""
        s_a = dynamic_act_scale(x) if aq is None else aq
        if self.kernel_mode == "pallas":
            from repro.kernels import ops  # deferred: kernels are optional

            return ops.quant_conv(
                x, qw, self.kh, self.kw, s_a,
                stride=_pair(self.stride), padding=self.padding,
            )
        return quant_conv_ref(
            quantize_array(x, s_a), qw, self.kh, self.kw, s_a,
            stride=_pair(self.stride), padding=self.padding,
        )

    def quant_serve(self, params: dict, x: jax.Array, *, relu: bool = False,
                    out_scale=None, residual=None, residual_scale=None,
                    bf=None, tile_h=None, tile_w=None) -> jax.Array:
        """One-kernel INT8 serving conv with the fused epilogue (§9).

        The whole layer — int8 conv, dequant, bias (from ``params``),
        optional ReLU, optional requantize at ``out_scale`` (the *next*
        layer's calibrated activation scale) — is a single kernel call
        (Pallas) or a single integer-oracle + :func:`quant_epilogue_ref`
        pass (ref mode). ``x`` may be fp (quantized at the calibrated
        ``aq`` or dynamically) or already int8-resident codes from the
        previous layer's epilogue (requires a calibrated ``aq``). Returns
        int8 codes when ``out_scale`` is given, fp32 otherwise.
        ``residual`` (int8 codes shaped like the output) at
        ``residual_scale`` is a residual block's shortcut, added in the
        same flush after the bias and before the ReLU.
        ``bf``/``tile_h``/``tile_w`` pin explicit launch tiles (the §10
        frozen-plan path); None keeps the registry/pick defaults.
        """
        qw = params["w"]
        aq = params.get("aq")
        b = params.get("b")
        if self.kernel_mode == "pallas":
            from repro.kernels import ops  # deferred: kernels are optional

            return ops.quant_conv(
                x, qw, self.kh, self.kw, aq, bias=b, relu=relu,
                out_scale=out_scale, residual=residual,
                residual_scale=residual_scale, stride=_pair(self.stride),
                padding=self.padding, bf=bf, tile_h=tile_h, tile_w=tile_w,
            )
        from repro.kernels.ref import quant_epilogue_ref, sparse_conv_int_ref

        xq, s_a = resolve_quant_input(x, aq)
        acc = sparse_conv_int_ref(
            xq, qw.as_dbb(), self.kh, self.kw,
            stride=_pair(self.stride), padding=self.padding,
        )
        return quant_epilogue_ref(
            acc, s_a * qw.scales, bias=b, relu=relu, out_scale=out_scale,
            residual=residual, residual_scale=residual_scale,
        )

    # ------------------------------------------------------- frozen plans
    def make_plan(self, params: dict, *, batch: int, h: int, w: int,
                  relu: bool = False, out_scale=None, fused: bool = False,
                  residual_scale=None):
        """Stage this layer's serving step once (DESIGN.md §10).

        Freezes the tiles of this exact launch (``kernels/core.py``'s
        ``default_conv_tiles``) and returns ``(run, tiles)``: ``run`` is an
        ``x -> y`` closure with the weight buffers
        frozen in that replicates exactly the path ``SparseCNN.apply``
        takes for these params (``fused=True`` = the §9 int8-resident
        chain step, so a plan built from calibrated quantized params is
        bit-identical to the unplanned chain); ``tiles`` is the resolved
        config (empty on reference/XLA paths). With ``residual_scale``
        (the §9 chain only) ``run`` is ``(x, residual) -> y``: the layer
        closes a residual block and adds the shortcut's int8 codes, at
        that scale, in its flush.
        """
        from repro.kernels.core import (
            conv_geometry, default_conv_tiles, default_interpret,
        )

        wp = params["w"]
        pallas = self.kernel_mode == "pallas"
        quant = isinstance(wp, QuantDBBWeight)
        compressed = isinstance(wp, DBBWeight)
        # fp stem fuses only on compiled backends — interpret-mode Pallas
        # dense conv loses badly to XLA's native conv, and the chain in
        # SparseCNN.apply makes the same call, keeping plan == apply
        # bit-identical (DESIGN.md §12)
        stem_fused = fused and pallas and out_scale is not None and not (
            quant or compressed) and not default_interpret()
        tiled = pallas and (quant or compressed or stem_fused)
        tiles: dict = {}
        if tiled:
            _, _, (ho, wo) = conv_geometry(h, w, self.kh, self.kw,
                                           self.stride, self.padding)
            if stem_fused:  # dense (kh, kw, C, F)
                block = (self.kh * self.kw * self.in_channels, wp.dtype.itemsize)
            else:
                from repro.kernels.vdbb_im2col_conv import weight_block

                block = weight_block(wp.values, wp.fmt)
            tiles = default_conv_tiles(ho, wo, self.out_channels, *block)
        if quant and fused and residual_scale is not None:
            def run(x, residual):
                return self.quant_serve(params, x, relu=relu,
                                        out_scale=out_scale, residual=residual,
                                        residual_scale=residual_scale, **tiles)
        elif quant and fused:
            def run(x):
                return self.quant_serve(params, x, relu=relu,
                                        out_scale=out_scale, **tiles)
        elif stem_fused:
            from repro.kernels import ops  # deferred: kernels are optional

            def run(x):
                return ops.fused_im2col_conv(
                    x, params["w"], bias=params.get("b"), relu=relu,
                    out_scale=out_scale, stride=_pair(self.stride),
                    padding=self.padding, **tiles,
                )
        elif tiled:
            from repro.kernels import ops  # deferred: kernels are optional

            # mirror __call__'s kernel → +bias order, with the tiles pinned
            # into the closure
            def run(x):
                if quant:
                    y = ops.quant_conv(
                        x, wp, self.kh, self.kw, params.get("aq"),
                        stride=_pair(self.stride), padding=self.padding,
                        **tiles,
                    )
                else:
                    y = ops.sparse_conv(
                        x, wp, self.kh, self.kw, stride=_pair(self.stride),
                        padding=self.padding, **tiles,
                    )
                if self.use_bias and "b" in params:
                    y = y + params["b"].astype(y.dtype)
                if relu:
                    y = jax.nn.relu(y)
                if out_scale is not None:
                    y = quantize_array(y, out_scale)
                return y
        else:
            # reference/XLA path: __call__ applies the bias itself
            def run(x):
                y = self(params, x)
                if relu:
                    y = jax.nn.relu(y)
                if out_scale is not None:
                    y = quantize_array(y, out_scale)
                return y
        return run, tiles

    # ------------------------------------------------------------------
    def constrain(self, params: dict, step=None, schedule: Optional[PruneSchedule] = None) -> dict:
        """Project the dense weight onto the (possibly annealed) constraint."""
        if self.fmt.is_dense or isinstance(params["w"], (DBBWeight, QuantDBBWeight)):
            return params
        if schedule is None or step is None:
            w = self._project(params["w"], self.fmt)
        else:
            cur = schedule.nnz_at(step, self.fmt)
            branches = [
                lambda w, n=n: self._project(w, dataclasses.replace(self.fmt, nnz=n))
                for n in range(self.fmt.nnz, self.fmt.bz + 1)
            ]
            w = jax.lax.switch(cur - self.fmt.nnz, branches, params["w"])
        return dict(params, w=w)

    def compress_params(self, params: dict) -> dict:
        if self.fmt.is_dense or isinstance(params["w"], (DBBWeight, QuantDBBWeight)):
            return params
        return dict(params, w=dbb_encode_conv(params["w"], self.fmt, prune=True))

    def quantize(self, params: dict, act_scale=None) -> dict:
        """Convert compressed params to the INT8 serving layout (§8);
        same contract as :meth:`DBBLinear.quantize` (dense layers — the
        stem — stay fp, like the paper's uncompressed first layer)."""
        w = params["w"]
        if isinstance(w, QuantDBBWeight):  # already int8: re-calibrate only
            if act_scale is None:
                return params
            return dict(params, aq=jnp.asarray(act_scale, jnp.float32))
        if not isinstance(w, DBBWeight):  # dense layer stays fp
            return params
        out = dict(params, w=quantize_dbb(w))
        if act_scale is not None:
            out["aq"] = jnp.asarray(act_scale, jnp.float32)
        return out

    # ------------------------------------------------------------------
    def out_hw(self, h: int, w: int) -> tuple:
        from repro.kernels.core import conv_geometry

        _, _, (ho, wo) = conv_geometry(h, w, self.kh, self.kw, self.stride, self.padding)
        return ho, wo

    def flops(self, batch: int, h: int, w: int) -> int:
        """Executed MACs*2 under the time-unrolled occupancy model."""
        ho, wo = self.out_hw(h, w)
        k = self.kh * self.kw * self.in_channels
        k_eff = (k // self.fmt.bz) * self.fmt.nnz
        return 2 * batch * ho * wo * k_eff * self.out_channels
