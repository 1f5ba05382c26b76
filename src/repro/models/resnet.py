"""SparseResNet — ResNet-50-style bottleneck CNNs on the paper's datapath.

The paper's workload is "AlexNet/ResNet-50-class CNNs"; this module serves
the ResNet family (He et al., arXiv:1512.03385, Table 1; the "v1.5" form,
torchvision's ``resnet50``, strides its downsampling blocks on the 3×3
conv). The layout:

* a 7×7/2 stem (C=3 → ``stem_channels``, dense fp32, padding 3) and a
  3×3/2 max-pool (padding 1);
* stages of bottleneck blocks: 1×1 ``c1`` (→ width) · 3×3 ``c2`` (stride
  2 in the first block of every stage after the first, padding 1) · 1×1
  ``c3`` (→ width·expansion), plus the shortcut: a 1×1 projection
  ``proj`` (with the block's stride) in each stage's first block, the
  identity elsewhere; ``relu(c3(...) + shortcut)`` closes the block;
* global average pooling and a ``DBBLinear`` head.

BatchNorm is folded into each conv's weight and bias, as int8 deployments
serve it. Every conv whose input channels divide into DBB blocks is VDBB
compressed; only the stem stays dense.

Params are named per layer: ``stem``, ``s{i}b{j}.c1`` / ``.c2`` / ``.c3``
/ ``.proj`` (stage i, block j, both from 1), ``fc``. The lifecycle
(compress, quantize, plan sets) is :class:`~repro.models.cnn.CNNLifecycle`.

Serving (DESIGN.md §9) is int8-resident: the stem's epilogue requantizes
at the first block's input scale, the max-pool runs on int8 codes (max
commutes with a positive-scale rounding), and each block's ``c3`` adds
the shortcut's int8 codes in its own flush — the identity's at the block
input's scale, a projection's at the scale its own flush requantized to —
then applies ReLU and requantizes at the next block's input scale. ``c1``
and the projection read the same codes, so they share one scale.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, Optional, Sequence

import jax
import jax.numpy as jnp

from repro.core.quant import QuantDBBWeight, act_scale_from_stats, quantize
from repro.core.sparse_conv import DBBConv2d
from repro.core.sparse_linear import DBBLinear
from repro.core.vdbb import DBBFormat, DENSE
from repro.models.cnn import CNNLifecycle

POINTWISE = ((0, 0), (0, 0))  # a 1×1 conv reads no padding
PAD1 = ((1, 1), (1, 1))  # 3×3 convs and the max-pool, at any stride


@dataclasses.dataclass(frozen=True)
class ResNetConfig:
    """Static description of a SparseResNet.

    stage_widths: the bottleneck (3×3) width of each stage; a block writes
    width·expansion channels. stage_blocks: blocks per stage.
    """

    name: str = "sparse-resnet"
    in_channels: int = 3
    image_size: int = 224
    stem_channels: int = 64
    stem_kernel: int = 7
    stage_widths: Sequence[int] = (64, 128, 256, 512)
    stage_blocks: Sequence[int] = (3, 4, 6, 3)
    expansion: int = 4
    num_classes: int = 1000
    dbb: Optional[DBBFormat] = None
    dtype: Any = jnp.float32
    kernel_mode: str = "ref"  # 'ref' | 'pallas'

    @property
    def fmt(self) -> DBBFormat:
        return self.dbb or DENSE


def max_pool(x: jax.Array) -> jax.Array:
    """3×3/2 max-pool, padding 1 (NHWC). The padding never wins: it is
    the dtype's lowest value, and int8 codes of a ReLU output are ≥ 0."""
    lo = (jnp.iinfo(x.dtype).min if jnp.issubdtype(x.dtype, jnp.integer)
          else -jnp.inf)
    return jax.lax.reduce_window(x, jnp.asarray(lo, x.dtype), jax.lax.max,
                                 (1, 3, 3, 1), (1, 2, 2, 1),
                                 ((0, 0), *PAD1, (0, 0)))


def pool_hw(h: int, w: int) -> tuple:
    return (h + 2 - 3) // 2 + 1, (w + 2 - 3) // 2 + 1


@dataclasses.dataclass(frozen=True)
class Bottleneck:
    """One bottleneck block: its convs, named as in the params tree."""

    name: str
    c1: DBBConv2d
    c2: DBBConv2d
    c3: DBBConv2d
    proj: Optional[DBBConv2d] = None

    def convs(self) -> list:
        out = [("c1", self.c1), ("c2", self.c2), ("c3", self.c3)]
        return out + ([("proj", self.proj)] if self.proj is not None else [])

    def out_hw(self, h: int, w: int) -> tuple:
        return self.c2.out_hw(h, w)

    def forward(self, x: jax.Array, *, runs: dict, fused: bool) -> jax.Array:
        """The block from its convs' serving closures ``runs[conv](x)``;
        every conv under its own scope (``c1`` … ``proj``). On the §9
        chain ``runs["c3"]`` takes the shortcut's int8 codes and adds
        them in its flush; otherwise c3 and the projection return conv +
        bias and the add and ReLU follow in fp32."""
        with jax.named_scope("c1"):
            h = runs["c1"](x)
        with jax.named_scope("c2"):
            h = runs["c2"](h)
        short = x
        if self.proj is not None:
            with jax.named_scope("proj"):
                short = runs["proj"](x)
        with jax.named_scope("c3"):
            if fused:
                return runs["c3"](h, short)
            return jax.nn.relu(runs["c3"](h) + short)

    def make_plan(self, params: dict, *, batch: int, h: int, w: int,
                  out_scale=None, fused: bool = False):
        """Stage the whole block once (DESIGN.md §10): each conv's
        ``make_plan`` with its tiles pinned, composed by :meth:`forward`.
        Returns ``(run, tiles)``, tiles keyed ``c1.bf`` and so on."""
        p = {k: params[f"{self.name}.{k}"] for k, _ in self.convs()}
        kw = dict(batch=batch, fused=fused)
        ho, wo = self.out_hw(h, w)
        specs = {
            "c1": dict(h=h, w=w, relu=True,
                       out_scale=p["c2"].get("aq") if fused else None),
            "c2": dict(h=h, w=w, relu=True,
                       out_scale=p["c3"].get("aq") if fused else None),
            # on the fp path the block adds the shortcut, then the ReLU
            "c3": dict(h=ho, w=wo, relu=fused, out_scale=out_scale),
        }
        if fused:
            specs["c3"]["residual_scale"] = self.shortcut_scale(params)
        if self.proj is not None:
            specs["proj"] = dict(h=h, w=w, relu=False,
                                 out_scale=p["proj"].get("oq") if fused else None)
        runs, tiles = {}, {}
        for k, m in self.convs():
            runs[k], t = m.make_plan(p[k], **specs[k], **kw)
            tiles.update({f"{k}.{name}": v for name, v in t.items()})
        return (lambda x: self.forward(x, runs=runs, fused=fused)), tiles

    def shortcut_scale(self, params: dict):
        """Scale of the shortcut's int8 codes on the §9 chain: the block
        input's (c1's) for the identity, the projection's requantize
        target for a projection."""
        if self.proj is not None:
            return params[f"{self.name}.proj"]["oq"]
        return params[f"{self.name}.c1"]["aq"]


@dataclasses.dataclass(frozen=True)
class SparseResNet(CNNLifecycle):
    cfg: ResNetConfig

    # ------------------------------------------------------------- defs
    def _conv(self, cin: int, cout: int, k: int, stride: int, padding) -> DBBConv2d:
        c = self.cfg
        return DBBConv2d(cin, cout, kernel_size=k, stride=stride, padding=padding,
                         fmt=c.fmt if cin % c.fmt.bz == 0 else DENSE,
                         use_bias=True, dtype=c.dtype, kernel_mode=c.kernel_mode)

    def stem(self) -> DBBConv2d:
        """The dense fp32 stem: XLA's own conv at full fp32 precision on
        every backend, its bias, ReLU and requantize fused by XLA (a
        Pallas conv would read the 3-channel input padded to 128 lanes:
        4.8 GB of temporaries at bucket 128, 224×224; PERF.md §3)."""
        c = self.cfg
        pad = c.stem_kernel // 2
        return DBBConv2d(c.in_channels, c.stem_channels, kernel_size=c.stem_kernel,
                         stride=2, padding=((pad, pad), (pad, pad)), fmt=DENSE,
                         use_bias=True, dtype=c.dtype, kernel_mode="ref")

    def blocks(self) -> list:
        c = self.cfg
        out, cin = [], c.stem_channels
        for si, (width, n) in enumerate(zip(c.stage_widths, c.stage_blocks)):
            cout = width * c.expansion
            for bi in range(n):
                stride = 2 if (si > 0 and bi == 0) else 1
                out.append(Bottleneck(
                    f"s{si + 1}b{bi + 1}",
                    c1=self._conv(cin, width, 1, 1, POINTWISE),
                    c2=self._conv(width, width, 3, stride, PAD1),
                    c3=self._conv(width, cout, 1, 1, POINTWISE),
                    proj=(self._conv(cin, cout, 1, stride, POINTWISE)
                          if bi == 0 else None),
                ))
                cin = cout
        return out

    def head(self) -> DBBLinear:
        c = self.cfg
        return DBBLinear(c.stage_widths[-1] * c.expansion, c.num_classes,
                         fmt=c.fmt, use_bias=True, dtype=c.dtype,
                         kernel_mode=c.kernel_mode)

    def named_layers(self) -> list:
        out = [("stem", self.stem())]
        for b in self.blocks():
            out += [(f"{b.name}.{k}", m) for k, m in b.convs()]
        return out + [("fc", self.head())]

    # ---------------------------------------------------------- forward
    def __call__(self, params: dict, x: jax.Array) -> jax.Array:
        """Inference forward. x: (N, H, W, C) -> logits (N, num_classes)."""
        return self.apply(params, x)

    def apply(self, params: dict, x: jax.Array, *, plan=None,
              collect_act_stats: bool = False,
              intermediates: Optional[list] = None):
        """Inference forward.

        Calibrated quantized params take the int8-resident chain (§9);
        anything else runs each layer in fp32 (decode + XLA conv on the
        ref path, the Pallas kernels on ``kernel_mode='pallas'``). With
        ``collect_act_stats=True`` (eager-only) returns ``(logits,
        stats)``: ``{name: ActStats}`` of the activation each layer reads,
        plus ``{block}.proj.out`` for each projection's output — what
        :meth:`quantize` takes. ``intermediates`` (eager-only) collects
        the stem's, the pool's and every block's output. ``plan`` serves
        through a frozen plan after checking it matches ``params``.
        """
        if plan is not None:
            if collect_act_stats or intermediates is not None:
                raise ValueError(
                    "plan serving is the frozen hot path; run without "
                    "plan= to collect stats or intermediates")
            plan.check(params)
            return plan.serve(x)
        if collect_act_stats:
            from repro.core.act_sparsity import measure_activation

            stats: dict = {}

            def observe(name, a):
                stats[name] = measure_activation(a, name=name)

            logits = self._forward_fp(params, x, observe, intermediates)
            return logits, stats
        if self._int8_chain_ready(params):
            return self._forward_int8(params, x, intermediates)
        return self._forward_fp(params, x, None, intermediates)

    def calibration_maxima(self, params: dict, x: jax.Array) -> dict:
        """``{name: max |input|}`` of every layer, and of each
        projection's output (``{block}.proj.out``), over ``x``: the
        calibration of :meth:`apply` ``(collect_act_stats=True)`` as one
        jittable pass."""
        out: dict = {}
        self._forward_fp(params, x,
                         lambda name, a: out.__setitem__(name, jnp.max(jnp.abs(a))),
                         None)
        return out

    def _forward_fp(self, params: dict, x: jax.Array,
                    observe: Optional[Callable], intermediates) -> jax.Array:
        see = observe or (lambda name, a: None)
        keep = (intermediates.append if intermediates is not None
                else (lambda a: None))
        see("stem", x)
        x = jax.nn.relu(self.stem()(params["stem"], x))
        keep(x)
        x = max_pool(x)
        keep(x)
        for b in self.blocks():
            see(f"{b.name}.c1", x)
            if b.proj is not None:
                see(f"{b.name}.proj", x)
            runs = {}
            for k, m in b.convs():
                name = f"{b.name}.{k}"

                def run(a, m=m, name=name, k=k):
                    if k in ("c2", "c3"):  # c1 and proj read the block input
                        see(name, a)
                    y = m(params[name], a)
                    if k == "proj":
                        see(f"{name}.out", y)
                    return jax.nn.relu(y) if k in ("c1", "c2") else y

                runs[k] = run
            x = b.forward(x, runs=runs, fused=False)
            keep(x)
        x = x.mean(axis=(1, 2))
        see("fc", x)
        return self.head()(params["fc"], x)

    # ----------------------------------- int8-resident serving chain (§9)
    def _int8_chain_ready(self, params: dict) -> bool:
        """True iff every compressed conv is quantized with a calibrated
        ``aq``, every projection carries its requantize target ``oq``, and
        the head is quantized with ``aq``."""
        for name, _ in self.named_layers()[1:]:  # the fp32 stem aside
            p = params.get(name, {})
            if not isinstance(p.get("w"), QuantDBBWeight) or "aq" not in p:
                return False
            if name.endswith(".proj") and "oq" not in p:
                return False
        return True

    def _scales(self, params: dict) -> list:
        """Per block, the scale its c3 requantizes to: the next block's
        input scale, None for the last (it flushes fp32 into pooling)."""
        blocks = self.blocks()
        return [params[f"{blocks[i + 1].name}.c1"]["aq"] if i + 1 < len(blocks)
                else None for i in range(len(blocks))]

    def _forward_int8(self, params: dict, x: jax.Array, intermediates) -> jax.Array:
        keep = (intermediates.append if intermediates is not None
                else (lambda a: None))
        blocks = self.blocks()
        x = jax.nn.relu(self.stem()(params["stem"], x))
        x = quantize(x, params[f"{blocks[0].name}.c1"]["aq"])
        keep(x)
        x = max_pool(x)
        keep(x)
        for b, out_scale in zip(blocks, self._scales(params)):
            p = {k: params[f"{b.name}.{k}"] for k, _ in b.convs()}
            runs = {
                "c1": lambda a, b=b, p=p: b.c1.quant_serve(
                    p["c1"], a, relu=True, out_scale=p["c2"]["aq"]),
                "c2": lambda a, b=b, p=p: b.c2.quant_serve(
                    p["c2"], a, relu=True, out_scale=p["c3"]["aq"]),
                "c3": lambda a, r, b=b, p=p, o=out_scale: b.c3.quant_serve(
                    p["c3"], a, relu=True, out_scale=o, residual=r,
                    residual_scale=b.shortcut_scale(params)),
            }
            if b.proj is not None:
                runs["proj"] = lambda a, b=b, p=p: b.proj.quant_serve(
                    p["proj"], a, out_scale=p["proj"]["oq"])
            x = b.forward(x, runs=runs, fused=True)
            keep(x)
        x = x.mean(axis=(1, 2))  # global average pool (fp32 flush above)
        return self.head().quant_serve(params["fc"], x)

    # ------------------------------------------- the paper's technique
    def quantize(self, params: dict, stats=None) -> dict:
        """:meth:`CNNLifecycle.quantize`, plus the shortcut scales: a
        block's projection reads the codes c1 reads, at c1's scale, and
        requantizes its output at ``oq`` from ``{block}.proj.out``'s
        ``absmax``. ``stats`` is the mapping :meth:`apply` collects."""
        if stats is not None:
            stats = dict(stats)
            for b in self.blocks():
                if b.proj is not None:
                    stats[f"{b.name}.proj"] = stats[f"{b.name}.c1"]
        out = super().quantize(params, stats)
        if stats is not None:
            for b in self.blocks():
                if b.proj is not None:
                    name = f"{b.name}.proj"
                    out[name] = dict(out[name], oq=jnp.asarray(
                        act_scale_from_stats(stats[f"{name}.out"]), jnp.float32))
        return out

    # ------------------------------------------- frozen serving plans (§10)
    def plan(self, params: dict, *, batch: int):
        """Freeze a serving plan (DESIGN.md §10): stages ``stem``,
        ``pool``, one per block (``s{i}b{j}``, each conv under its own
        scope inside), ``gap`` and ``fc`` — the path :meth:`apply` takes
        for these params, bit for bit."""
        from repro.models.plan import PlanBuilder

        c = self.cfg
        fused = self._int8_chain_ready(params)
        blocks = self.blocks()
        pb = PlanBuilder(c.name, params, batch=batch,
                         sample_spec=((c.image_size, c.image_size,
                                       c.in_channels), "float32"))
        stem = self.stem()
        pb.stage("stem", "conv", stem.make_plan, params["stem"], batch=batch,
                 h=c.image_size, w=c.image_size, relu=True, fused=fused,
                 out_scale=params[f"{blocks[0].name}.c1"]["aq"] if fused else None)
        h, w = pool_hw(*stem.out_hw(c.image_size, c.image_size))
        pb.raw("pool", "pool", max_pool)
        for b, out_scale in zip(blocks, self._scales(params) if fused
                                else [None] * len(blocks)):
            pb.stage(b.name, "block", b.make_plan, params, batch=batch, h=h, w=w,
                     out_scale=out_scale, fused=fused)
            h, w = b.out_hw(h, w)
        pb.raw("gap", "pool", lambda x: x.mean(axis=(1, 2)))
        pb.stage("fc", "linear", self.head().make_plan, params["fc"],
                 batch=batch, fused=fused)
        return pb.build()
