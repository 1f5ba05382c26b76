"""DBBLinear — the paper's technique as a first-class model layer.

Training: weights are dense arrays kept *projected* onto the DBB constraint
(magnitude top-nnz per block) by `constrain()` — applied after optimizer
updates, mirroring the paper's magnitude-based DBB-aware pruning (§V-A).
A progressive schedule anneals nnz from bz down to the target.

Serving: `compress_params()` converts the dense weight to the compressed
DBBWeight layout; the forward pass then runs the compressed matmul
(Pallas kernel on TPU, jnp reference elsewhere), consuming nnz/bz of the
dense weight bandwidth — the VDBB win. `quantize()` (DESIGN.md §8)
further converts compressed params to the ASIC's INT8 numerics: int8
values + per-output-channel scales (`QuantDBBWeight`), per-tensor
activation quantization (calibrated or dynamic), exact int32
accumulation, dequantization at the accumulator flush.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Optional

import jax
import jax.numpy as jnp

from repro.core.quant import (
    QuantDBBWeight,
    dynamic_act_scale,
    int_matmul_ref,
    quant_matmul_ref,
    quantize as quantize_array,
    quantize_dbb,
    resolve_quant_input,
)
from repro.core.vdbb import (
    DBBFormat,
    DBBWeight,
    DENSE,
    dbb_decode,
    dbb_encode,
    dbb_matmul_gather_ref,
    dbb_prune,
)


@dataclasses.dataclass(frozen=True)
class PruneSchedule:
    """Linear anneal of nnz from bz to target between begin and end steps."""

    begin_step: int = 0
    end_step: int = 1
    constrain_every: int = 1  # re-project every k steps (1 = every step)

    def nnz_at(self, step: int, fmt: DBBFormat) -> jax.Array:
        """Traced-safe current density bound (int32 scalar)."""
        frac = jnp.clip(
            (step - self.begin_step) / max(self.end_step - self.begin_step, 1), 0.0, 1.0
        )
        cur = jnp.round(fmt.bz - frac * (fmt.bz - fmt.nnz)).astype(jnp.int32)
        return cur


@dataclasses.dataclass(frozen=True)
class DBBLinear:
    """y = x @ W (+ b); W is (in_features, out_features), DBB along K=in."""

    in_features: int
    out_features: int
    fmt: DBBFormat = DENSE
    use_bias: bool = False
    dtype: Any = jnp.float32
    kernel_mode: str = "ref"  # 'ref' | 'pallas' (serving path choice)

    def init(self, key) -> dict:
        scale = 1.0 / (self.in_features**0.5)
        w = scale * jax.random.truncated_normal(
            key, -2, 2, (self.in_features, self.out_features), self.dtype
        )
        if not self.fmt.is_dense:
            w = dbb_prune(w, self.fmt)
        p = {"w": w}
        if self.use_bias:
            p["b"] = jnp.zeros((self.out_features,), self.dtype)
        return p

    # ------------------------------------------------------------------
    def __call__(self, params: dict, x: jax.Array) -> jax.Array:
        w = params["w"]
        if isinstance(w, QuantDBBWeight):
            y = self._quantized_matmul(x, w, params.get("aq"))
        elif isinstance(w, DBBWeight):
            y = self._compressed_matmul(x, w)
        else:
            y = jnp.matmul(x, w.astype(x.dtype))
        if self.use_bias:
            y = y + params["b"].astype(y.dtype)
        return y

    def _use_pallas(self, m: int) -> bool:
        """Pallas serving path, with the tiny-M reference fallback: below
        the MXU sublane (8 rows) a Pallas launch wastes the array, so the
        classifier-head-sized GEMMs stay on the jnp reference."""
        return self.kernel_mode == "pallas" and m >= 8

    def _compressed_matmul(self, x: jax.Array, w: DBBWeight) -> jax.Array:
        lead = x.shape[:-1]
        x2 = x.reshape(-1, x.shape[-1])
        if self._use_pallas(x2.shape[0]):
            from repro.kernels import ops  # deferred: kernels are optional

            y2 = ops.vdbb_matmul(x2, w)
        elif w.fmt.group_size(w.shape[1]) == w.shape[1]:
            y2 = dbb_matmul_gather_ref(x2, w)
        else:
            y2 = jnp.matmul(x2, dbb_decode(w).astype(x.dtype))
        return y2.reshape(*lead, self.out_features)

    def _quantized_matmul(self, x: jax.Array, qw: QuantDBBWeight, aq) -> jax.Array:
        """INT8 serving matmul: per-tensor act quant (calibrated ``aq`` or
        dynamic), int8 kernel / integer reference, fp32 out (bias after)."""
        lead = x.shape[:-1]
        x2 = x.reshape(-1, x.shape[-1])
        s_a = dynamic_act_scale(x2) if aq is None else aq
        if self._use_pallas(x2.shape[0]):
            from repro.kernels import ops  # deferred: kernels are optional

            y2 = ops.quant_matmul(x2, qw, s_a)
        else:
            y2 = quant_matmul_ref(quantize_array(x2, s_a), qw, s_a)
        return y2.reshape(*lead, self.out_features)

    def quant_serve(self, params: dict, x: jax.Array, *, relu: bool = False,
                    out_scale=None, bm=None, bn=None, kb=None) -> jax.Array:
        """One-kernel INT8 serving GEMM with the fused epilogue (§9).

        Mirrors :meth:`DBBConv2d.quant_serve`: int8 GEMM, dequant, bias,
        optional ReLU and requantize at ``out_scale`` in a single kernel
        (Pallas) or one integer-oracle + ``quant_epilogue_ref`` pass (ref
        mode / tiny-M fallback). ``x`` may be fp or int8-resident codes
        (the latter requires a calibrated ``aq``). ``bm``/``bn``/``kb``
        pin explicit launch tiles (the §10 frozen-plan path); None keeps
        the registry/pick defaults.
        """
        qw = params["w"]
        aq = params.get("aq")
        b = params.get("b")
        lead = x.shape[:-1]
        x2 = x.reshape(-1, x.shape[-1])
        if self._use_pallas(x2.shape[0]):
            from repro.kernels import ops  # deferred: kernels are optional

            y2 = ops.quant_matmul(x2, qw, aq, bias=b, relu=relu,
                                  out_scale=out_scale, bm=bm, bn=bn, kb=kb)
        else:
            from repro.kernels.ref import quant_epilogue_ref

            xq, s_a = resolve_quant_input(x2, aq)
            acc = int_matmul_ref(xq, dbb_decode(qw.as_dbb()))
            y2 = quant_epilogue_ref(
                acc, s_a * qw.scales, bias=b, relu=relu, out_scale=out_scale
            )
        return y2.reshape(*lead, self.out_features)

    # ------------------------------------------------------- frozen plans
    def make_plan(self, params: dict, *, batch: int, relu: bool = False,
                  out_scale=None, fused: bool = False):
        """Stage this layer's serving step once (DESIGN.md §10); the GEMM
        twin of :meth:`DBBConv2d.make_plan`, freezing
        ``kernels/core.py``'s ``default_matmul_tiles``. ``batch`` is the
        GEMM's M (the tiny-M reference fallback applies, so
        classifier-head-sized plans carry no tiles). Returns ``(run,
        tiles)``."""
        from repro.kernels.core import default_matmul_tiles

        wp = params["w"]
        quant = isinstance(wp, QuantDBBWeight)
        tiled = self._use_pallas(batch) and isinstance(wp, (DBBWeight, QuantDBBWeight))
        if tiled:
            nb, rem = divmod(self.in_features, wp.fmt.bz)
            if rem:
                raise ValueError(
                    f"DBBLinear.make_plan: in_features={self.in_features} is "
                    f"not a multiple of the DBB block size bz={wp.fmt.bz} "
                    f"(ragged K has no compressed-block layout; pad K or "
                    f"serve with kernel_mode='ref')")
        tiles: dict = {}
        if tiled:
            tiles = default_matmul_tiles(
                batch, self.in_features, self.out_features, wp.fmt.bz,
                jnp.int8 if quant else self.dtype)
        if quant and fused:
            def run(x):
                return self.quant_serve(params, x, relu=relu,
                                        out_scale=out_scale, **tiles)
        elif tiled:
            from repro.kernels import ops  # deferred: kernels are optional

            # mirror __call__'s GEMM → +bias order, tiles pinned in
            def run(x):
                lead = x.shape[:-1]
                x2 = x.reshape(-1, x.shape[-1])
                if quant:
                    y2 = ops.quant_matmul(x2, wp, params.get("aq"), **tiles)
                else:
                    y2 = ops.vdbb_matmul(x2, wp, **tiles)
                y = y2.reshape(*lead, self.out_features)
                if self.use_bias and "b" in params:
                    y = y + params["b"].astype(y.dtype)
                if relu:
                    y = jax.nn.relu(y)
                if out_scale is not None:
                    y = quantize_array(y, out_scale)
                return y
        else:
            # reference path (incl. the tiny-M fallback): __call__ applies
            # the bias itself
            def run(x):
                y = self(params, x)
                if relu:
                    y = jax.nn.relu(y)
                if out_scale is not None:  # mirror the conv twin's fallback
                    y = quantize_array(y, out_scale)
                return y
        return run, tiles

    # ------------------------------------------------------------------
    def constrain(self, params: dict, step=None, schedule: Optional[PruneSchedule] = None) -> dict:
        """Project the dense weight onto the (possibly annealed) constraint."""
        if self.fmt.is_dense or isinstance(params["w"], (DBBWeight, QuantDBBWeight)):
            return params
        if schedule is None or step is None:
            w = dbb_prune(params["w"], self.fmt)
        else:
            # anneal: switch between per-nnz masks with a traced nnz.
            cur = schedule.nnz_at(step, self.fmt)
            branches = [
                lambda w, n=n: dbb_prune(
                    w, dataclasses.replace(self.fmt, nnz=n)
                )
                for n in range(self.fmt.nnz, self.fmt.bz + 1)
            ]
            w = jax.lax.switch(cur - self.fmt.nnz, branches, params["w"])
        return dict(params, w=w)

    def compress_params(self, params: dict) -> dict:
        if self.fmt.is_dense or isinstance(params["w"], (DBBWeight, QuantDBBWeight)):
            return params
        return dict(params, w=dbb_encode(params["w"], self.fmt, prune=True))

    def quantize(self, params: dict, act_scale=None) -> dict:
        """Convert compressed params to the INT8 serving layout (§8).

        ``act_scale``: static per-tensor activation scale from calibration
        (``quant.act_scale_from_stats``); None keeps activation
        quantization dynamic (scale from each live batch). Dense
        (non-compressed) layers are returned unchanged — they stay fp, like
        the paper's uncompressed stem.
        """
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

    def param_specs(self, k_axis: str, n_axis: str) -> dict:
        """Logical sharding axes for dense or compressed layouts."""
        spec = {"w": (k_axis, n_axis)}
        if self.use_bias:
            spec["b"] = (n_axis,)
        return spec

    def flops(self, batch: int) -> int:
        """Executed MACs*2 under the time-unrolled occupancy model."""
        k_eff = (self.in_features // self.fmt.bz) * self.fmt.nnz
        return 2 * batch * k_eff * self.out_features
