"""SparseCNN — a small CNN inference model on the paper's datapath.

The paper's workload is sparse CNN inference (its Table I/II models are
AlexNet/ResNet-50-class CNNs). This module provides that workload as a
first-class model next to the LM zoo: a VGG-style stack of DBBConv2d
stages (conv → ReLU, stride-2 downsample between stages) closed by global
average pooling and a DBBLinear classifier head.

Same lifecycle as the LM (train → constrain → compress), plus the INT8
serving step: ``constrain()`` projects every conv/linear weight onto the
DBB constraint, ``compress()`` converts them to the compressed DBBWeight
layout, ``quantize()`` (optionally calibrated by the stats from
``apply(collect_act_stats=True)``) converts to the ASIC's INT8 numerics
(DESIGN.md §8), and the forward pass then runs the fused IM2COL × VDBB
conv per layer (``kernel_mode='pallas'``) or the decode + XLA conv
reference path.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Mapping, Optional, Sequence

import jax
import jax.numpy as jnp

from repro.core.quant import QuantDBBWeight, quantize
from repro.core.sparse_conv import DBBConv2d
from repro.core.sparse_linear import DBBLinear, PruneSchedule
from repro.core.vdbb import DBBFormat, DENSE
from repro.kernels.core import _pair, default_interpret


class CNNLifecycle:
    """The lifecycle the CNN families share, over their named layers.

    A family (a frozen dataclass with a ``cfg`` that has ``name`` and
    ``kernel_mode``) defines :meth:`named_layers` — ``[(name, module)]``
    in forward order, each module a :class:`DBBConv2d` or
    :class:`DBBLinear` whose params sit at ``params[name]`` — and
    ``plan(params, *, batch, ...)``; constrain, compress, quantize (with
    its calibrated scale per layer), the bucketed plan set and its
    reference fallback follow from those.
    """

    def named_layers(self) -> list:
        raise NotImplementedError

    def init(self, key) -> dict:
        layers = self.named_layers()
        keys = jax.random.split(key, len(layers))
        return {name: m.init(k) for (name, m), k in zip(layers, keys)}

    def constrain(self, params: dict, step=None, schedule: Optional[PruneSchedule] = None) -> dict:
        return {name: m.constrain(params[name], step, schedule)
                for name, m in self.named_layers()}

    def compress(self, params: dict) -> dict:
        return {name: m.compress_params(params[name]) for name, m in self.named_layers()}

    def quantize(self, params: dict, stats=None) -> dict:
        """INT8 serving conversion of compressed params (DESIGN.md §8).

        ``stats`` (optional): calibration :class:`ActStats` — one per layer
        in :meth:`named_layers` order, or a mapping from layer name —
        measured on the activation each layer *reads*, whose ``absmax``
        becomes that layer's static per-tensor activation scale. Without
        stats, activation scales are dynamic (computed per batch). Dense
        layers (the C=3 stem) stay fp32, like the paper's uncompressed
        first layer.
        """
        from repro.core.quant import act_scale_from_stats

        layers = self.named_layers()
        if stats is not None and not isinstance(stats, Mapping):
            if len(stats) != len(layers):
                raise ValueError(
                    f"calibration stats for {len(stats)} layers, model has {len(layers)}"
                )
            stats = {name: st for (name, _), st in zip(layers, stats)}
        return {
            name: m.quantize(params[name], act_scale=(
                None if stats is None else act_scale_from_stats(stats[name])))
            for name, m in layers
        }

    def plan_set(self, params: dict, *, max_batch: Optional[int] = None,
                 buckets: Optional[Sequence[int]] = None, dp: int = 1,
                 tune: str = "cache"):
        """Freeze a bucketed serving plan set (DESIGN.md §11).

        One ``plan`` per batch-size bucket, all sharing the same params
        fingerprint. ``buckets`` defaults to the
        power-of-two ladder ``make_buckets(max_batch, dp=dp)``; ``dp``
        (the data-parallel degree the set will be served at) forces
        every bucket to shard evenly over a mesh's data axis. The
        returned :class:`~repro.models.plan.PlanSet` serves any batch
        size retrace-free after warmup: ragged batches pad up to the
        nearest bucket and slice back, bit-identical to per-request
        serving.

        ``tune`` is kept only for the benchmark's callers
        (``benchmarks/chip/``), which pass ``tune="cache"``: tiles come
        from ``kernels/core.py``'s rule, and any other value raises
        ``ValueError``.
        """
        from repro.models.plan import build_plan_set

        if tune != "cache":
            raise ValueError(
                f"tune={tune!r}: tiles come from kernels/core.py's rule "
                "(default_matmul_tiles / default_conv_tiles); only 'cache' "
                "is accepted")
        return build_plan_set(
            self.cfg.name, params, lambda b: self.plan(params, batch=b),
            max_batch=max_batch, buckets=buckets, dp=dp,
        )

    def fallback_plan_set(self, params: dict, primary, *, verify: bool = True):
        """Per-bucket degradation closures for the §15 self-healing tier:
        re-stage ``primary``'s bucket ladder on the reference
        (gather/integer-oracle) kernel path from the *same* quantized
        params, verify bit-compat per bucket, and return the
        ``{bucket: serve}`` mapping ``CNNServer(fallback=...)`` consumes.
        The params fingerprint is content-based, so the ref restage pins
        to the identical weights — a demoted bucket serves the same
        numbers through a different backend, not a different model."""
        from repro.models.plan import fallback_closures

        ref_model = type(self)(dataclasses.replace(self.cfg, kernel_mode="ref"))
        ref_set = ref_model.plan_set(params, buckets=primary.buckets)
        return fallback_closures(primary, ref_set, verify=verify)


@dataclasses.dataclass(frozen=True)
class CNNConfig:
    """Static description of a SparseCNN.

    stage_channels: output channels per stage; stage i > 0 downsamples 2×.
    convs_per_stage: conv layers in each stage (first one carries the stride).
    """

    name: str = "sparse-cnn"
    in_channels: int = 3
    image_size: int = 32
    stage_channels: Sequence[int] = (32, 64, 128)
    convs_per_stage: int = 2
    kernel_size: int = 3
    num_classes: int = 10
    dbb: Optional[DBBFormat] = None
    dtype: Any = jnp.float32
    kernel_mode: str = "ref"  # 'ref' | 'pallas'

    @property
    def fmt(self) -> DBBFormat:
        return self.dbb or DENSE

    def param_count(self) -> int:
        total = 0
        for layer in SparseCNN(self).layers():
            if isinstance(layer, DBBConv2d):
                total += layer.kh * layer.kw * layer.in_channels * layer.out_channels
            elif isinstance(layer, DBBLinear):
                total += layer.in_features * layer.out_features
        return total


@dataclasses.dataclass(frozen=True)
class SparseCNN(CNNLifecycle):
    cfg: CNNConfig

    # ------------------------------------------------------------- defs
    def layers(self):
        """Ordered (conv... , linear head) layer modules."""
        c = self.cfg
        out = []
        prev = c.in_channels
        for si, ch in enumerate(c.stage_channels):
            for li in range(c.convs_per_stage):
                stride = 2 if (si > 0 and li == 0) else 1
                # the stem (prev == in_channels) stays dense: C=3 is not
                # bz-blockable, matching the paper's uncompressed first layer.
                fmt = c.fmt if prev % c.fmt.bz == 0 else DENSE
                out.append(
                    DBBConv2d(
                        prev, ch, kernel_size=c.kernel_size, stride=stride,
                        padding="SAME", fmt=fmt, use_bias=True, dtype=c.dtype,
                        kernel_mode=c.kernel_mode,
                    )
                )
                prev = ch
        out.append(
            DBBLinear(
                prev, c.num_classes, fmt=c.fmt, use_bias=True, dtype=c.dtype,
                # head GEMM follows the model's kernel mode; DBBLinear
                # itself falls back to the reference for tiny M (< the
                # MXU sublane), so small batches never waste a launch.
                kernel_mode=c.kernel_mode,
            )
        )
        return out

    def named_layers(self) -> list:
        return [(f"l{i}", m) for i, m in enumerate(self.layers())]

    # ---------------------------------------------------------- forward
    def __call__(self, params: dict, x: jax.Array) -> jax.Array:
        """Inference forward. x: (N, H, W, C) -> logits (N, num_classes)."""
        return self.apply(params, x)

    def apply(
        self,
        params: dict,
        x: jax.Array,
        *,
        plan=None,
        collect_act_stats: bool = False,
        act_threshold: float = 0.0,
        intermediates: Optional[list] = None,
    ):
        """Inference forward, optionally measuring activation sparsity.

        With ``collect_act_stats=True`` (eager-only; DESIGN.md §7) returns
        ``(logits, stats)`` where ``stats`` is one
        :class:`repro.core.act_sparsity.ActStats` per layer, measured on
        the activation each layer *reads* (the tensor the IM2COL unit /
        GEMM streams), MAC-weighted for whole-model composition.

        Calibrated quantized params (every compressed layer carrying a
        static ``aq`` act scale) take the **int8-resident** serving chain
        (DESIGN.md §9): each layer is one fused kernel whose epilogue
        requantizes straight to the next layer's int8 codes — no
        standalone fp32 dequant/ReLU/requant passes between compressed
        layers. ``intermediates`` (optional list, eager-only) collects
        each inter-layer activation so callers can assert dtypes.

        ``plan`` (a :class:`repro.models.plan.ModelPlan` from
        :meth:`plan`, DESIGN.md §10) serves through the frozen staged
        chain after checking the plan still matches ``params``
        (:class:`~repro.models.plan.StalePlanError` otherwise); the
        check-free hot path is ``plan.serve(x)`` directly.
        """
        if plan is not None:
            if collect_act_stats or intermediates is not None:
                raise ValueError(
                    "plan serving is the frozen hot path; run without "
                    "plan= to collect stats or intermediates"
                )
            plan.check(params)
            return plan.serve(x)
        layers = self.layers()
        if not collect_act_stats and self._int8_chain_ready(layers, params):
            return self._apply_int8_resident(layers, params, x, intermediates)
        stats = []
        if collect_act_stats:
            from repro.core.act_sparsity import measure_activation

            h, w = x.shape[1], x.shape[2]
        for i, m in enumerate(layers[:-1]):
            if collect_act_stats:
                stats.append(
                    measure_activation(
                        x, name=f"l{i}", threshold=act_threshold,
                        macs=m.flops(x.shape[0], h, w) // 2,
                    )
                )
                h, w = m.out_hw(h, w)
            x = jax.nn.relu(m(params[f"l{i}"], x))
            if intermediates is not None:
                intermediates.append(x)
        x = x.mean(axis=(1, 2))  # global average pool
        head = layers[-1]
        if collect_act_stats:
            stats.append(
                measure_activation(
                    x, name=f"l{len(layers) - 1}", threshold=act_threshold,
                    macs=head.flops(x.shape[0]) // 2,
                )
            )
        logits = head(params[f"l{len(layers) - 1}"], x)
        if collect_act_stats:
            return logits, tuple(stats)
        return logits

    # ----------------------------------- int8-resident serving chain (§9)
    def _int8_chain_ready(self, layers, params: dict) -> bool:
        """True iff serving can run int8-resident end to end: every
        compressed conv after the (possibly fp) stem is quantized with a
        calibrated static ``aq`` (needed both to read int8 codes and as
        the previous layer's requantize target), and the head is
        quantized. Anything else falls back to the per-layer fp path."""
        any_quant = False
        for i, m in enumerate(layers[:-1]):
            p = params.get(f"l{i}", {})
            w = p.get("w")
            if isinstance(w, QuantDBBWeight):
                if "aq" not in p:
                    return False
                any_quant = True
            elif i > 0:  # a mid-chain fp layer would need a dequant pass
                return False
        head = params.get(f"l{len(layers) - 1}", {})
        return any_quant and isinstance(head.get("w"), QuantDBBWeight)

    def _apply_int8_resident(self, layers, params: dict, x: jax.Array,
                             intermediates: Optional[list] = None) -> jax.Array:
        """One fused kernel per layer, int8 activations in between (§9).

        Every compressed conv consumes the previous layer's int8 codes
        and its epilogue (dequant · bias · ReLU · requant at the next
        layer's calibrated scale) emits the next codes straight from the
        accumulator flush. The fp32 stem fuses bias + ReLU + the first
        requantize into its own kernel on the Pallas path (one standalone
        quantize pass on the ref path); the last conv flushes fp32
        (bias + ReLU still fused) into global average pooling, and the
        quantized head GEMM (bias fused) produces the fp32 logits.
        """
        convs, head = layers[:-1], layers[-1]
        n = len(convs)
        for i, m in enumerate(convs):
            p = params[f"l{i}"]
            out_scale = params[f"l{i + 1}"]["aq"] if i + 1 < n else None
            if isinstance(p["w"], QuantDBBWeight):
                x = m.quant_serve(p, x, relu=True, out_scale=out_scale)
            elif m.kernel_mode == "pallas" and out_scale is not None \
                    and not default_interpret():
                # fp stem, one kernel: dense conv with the fused epilogue
                # (compiled backends only — interpret-mode Pallas dense
                # conv is far slower than XLA's native conv on CPU, so
                # there the ref-path conv + standalone quantize wins;
                # DESIGN.md §12)
                from repro.kernels import ops  # deferred: kernels are optional

                x = ops.fused_im2col_conv(
                    x, p["w"], bias=p.get("b"), relu=True, out_scale=out_scale,
                    stride=_pair(m.stride), padding=m.padding,
                )
            else:
                # fp stem, ref path: conv (+bias) · ReLU · one int8
                # quantize at the next layer's calibrated scale — the only
                # standalone fp32 activation pass in the chain.
                x = jax.nn.relu(m(p, x))
                if out_scale is not None:
                    x = quantize(x, out_scale)
            if intermediates is not None:
                intermediates.append(x)
        x = x.mean(axis=(1, 2))  # global average pool (fp32 flush above)
        return head.quant_serve(params[f"l{n}"], x)

    # ------------------------------------------- frozen serving plans (§10)
    def plan(self, params: dict, *, batch: int):
        """Freeze a once-per-model serving plan (DESIGN.md §10).

        Freezes every layer's tiles (``kernels/core.py``'s rule, at this
        layer's launch shape), stages each layer's serving closure with
        its weight buffers frozen in —
        replicating exactly the path :meth:`apply` takes for these params,
        including the §9 int8-resident chain when calibrated quantized
        params are detected — and jit-compiles the whole chain once.
        Steady-state serving (``plan.serve(x)``) is then a single dispatch
        with zero per-call tile resolution, weight re-layout, or
        retracing. The plan is immutable and pinned to ``params`` by
        content fingerprint; serving through :meth:`apply`'s ``plan=``
        kwarg re-checks that pin.

        ``batch`` fixes the input batch size the plan is staged for —
        other batch shapes still run, but retrace with the tiles frozen
        for ``batch``.
        """
        from repro.models.plan import PlanBuilder

        layers = self.layers()
        convs, head = layers[:-1], layers[-1]
        fused = self._int8_chain_ready(layers, params)
        c = self.cfg
        h = w = c.image_size
        n = len(convs)
        pb = PlanBuilder(c.name, params, batch=batch,
                         sample_spec=((c.image_size, c.image_size,
                                       c.in_channels), "float32"))
        for i, m in enumerate(convs):
            out_scale = None
            if fused and i + 1 < n:
                out_scale = params[f"l{i + 1}"]["aq"]
            pb.stage(f"l{i}", "conv", m.make_plan, params[f"l{i}"],
                     batch=batch, h=h, w=w, relu=True, out_scale=out_scale,
                     fused=fused)
            h, w = m.out_hw(h, w)
        pb.raw("gap", "pool", lambda x: x.mean(axis=(1, 2)))
        pb.stage(f"l{n}", "linear", head.make_plan, params[f"l{n}"],
                 batch=batch, fused=fused)
        return pb.build()

    # ------------------------------------------------------------ costs
    def layer_costs(self, batch: int, *, bits: int = 8, act_bits=None,
                    stats=None, epilogue_fused: bool = False) -> list:
        """Per-conv-layer ``dbb_conv_costs`` dicts for this model.

        ``stats`` (optional): per-layer ActStats from
        ``apply(collect_act_stats=True)`` — layer i's measured activation
        sparsity is recorded into its cost dict, ready for
        ``energy_model.model_workload``. ``bits``/``act_bits`` are the
        operand widths (8 = the INT8 serving path of ``quantize()``);
        ``epilogue_fused`` accounts the §9 fused epilogue (int8 flush, no
        standalone dequant/requant passes). Returns (name, costs, fmt)
        triples.
        """
        from repro.core.vdbb import dbb_conv_costs

        c = self.cfg
        h = w = c.image_size
        out = []
        for i, m in enumerate(self.layers()):
            if not isinstance(m, DBBConv2d):
                continue
            act = stats[i] if stats is not None else None
            out.append(
                (
                    f"l{i}",
                    dbb_conv_costs(
                        batch, h, w, m.in_channels, m.out_channels, m.kh, m.kw,
                        m.fmt, stride=m.stride, padding=m.padding, bits=bits,
                        act_bits=act_bits, act=act, epilogue_fused=epilogue_fused,
                    ),
                    m.fmt,
                )
            )
            h, w = m.out_hw(h, w)
        return out

    def flops(self, batch: int) -> int:
        """Executed MACs*2 under the time-unrolled occupancy model."""
        c = self.cfg
        h = w = c.image_size
        total = 0
        for m in self.layers():
            if isinstance(m, DBBConv2d):
                total += m.flops(batch, h, w)
                h, w = m.out_hw(h, w)
            else:
                total += m.flops(batch)
        return total
