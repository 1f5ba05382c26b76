"""Operations and bytes of each layer of a served CNN, from its shapes alone.

The yardstick for the roofline and peak shares: what the algorithm needs,
whatever implements it. A compressed layer counts only the MACs of the
non-zeros it keeps (K·nnz/bz per output), so a kernel that does extra work
(a selection matmul, padded tiles) gets no credit for it. Bytes are each
operand once: the raw input activation, the compressed weight values and
their position indices, the per-channel epilogue rows (scale, bias) and
the output.
"""
from __future__ import annotations

import math

# operand widths on the served int8 chain: the stem reads fp32 images and
# writes int8 codes; compressed convs read int8 and write int8 except the
# last, which flushes fp32 into global average pooling; the head reads
# int8 codes of the pooled vector and writes fp32 logits
F32, I8 = 4, 1


def layers(config: dict) -> list:
    """One dict per served layer, in order: stem, compressed convs, head."""
    dbb = config["dbb"]
    bz, nnz, group = dbb["bz"], dbb["nnz"], dbb["group"]
    k = config["kernel_size"]
    h = w = config["image_size"]
    cin = config["in_channels"]
    convs = []
    for si, ch in enumerate(config["stage_channels"]):
        for li in range(config["convs_per_stage"]):
            stride = 2 if (si > 0 and li == 0) else 1
            ho, wo = math.ceil(h / stride), math.ceil(w / stride)  # SAME
            convs.append(dict(kind="conv", h=h, w=w, cin=cin, cout=ch, k=k,
                              stride=stride, ho=ho, wo=wo,
                              compressed=cin % bz == 0))
            h, w, cin = ho, wo, ch
    out = []
    for i, c in enumerate(convs):
        last = i == len(convs) - 1
        c.update(name=f"l{i}", in_bytes=I8 if c["compressed"] else F32,
                 out_bytes=F32 if last else I8)
        if not c["compressed"]:
            c["kind"] = "stem"
        out.append(c)
    out.append(dict(name=f"l{len(convs)}", kind="head", h=1, w=1, cin=cin,
                    cout=config["num_classes"], k=1, stride=1, ho=1, wo=1,
                    compressed=cin % bz == 0, in_bytes=I8, out_bytes=F32))
    for layer in out:
        layer.update(bz=bz, nnz=nnz if layer["compressed"] else bz,
                     group=group)
    return out


def reduction(layer: dict) -> int:
    """K that the layer needs per output: kh·kw·C, times nnz/bz if compressed."""
    k = layer["k"] * layer["k"] * layer["cin"]
    return k // layer["bz"] * layer["nnz"] if layer["compressed"] else k


def ops_per_image(layer: dict) -> int:
    """2·M·K_eff·N for one image: M = Ho·Wo output positions."""
    return 2 * layer["ho"] * layer["wo"] * reduction(layer) * layer["cout"]


def weight_bytes(layer: dict) -> int:
    """Stored weight stream: values, position indices (int8; one index per
    kept value and column group), epilogue scale and bias rows (fp32)."""
    k = layer["k"] * layer["k"] * layer["cin"]
    if not layer["compressed"]:
        return k * layer["cout"] * F32 + layer["cout"] * F32
    kept = reduction(layer)
    groups = 1 if layer["group"] == "matrix" else layer["cout"]
    return kept * layer["cout"] * I8 + kept * groups * I8 + 2 * layer["cout"] * F32


def bytes_per_call(layer: dict, batch: int) -> int:
    act_in = batch * layer["h"] * layer["w"] * layer["cin"] * layer["in_bytes"]
    act_out = batch * layer["ho"] * layer["wo"] * layer["cout"] * layer["out_bytes"]
    return act_in + weight_bytes(layer) + act_out


def peak_ops(layer: dict, peaks: dict) -> float:
    """int8 peak for compressed layers; the fp32 stem against the bf16
    peak, since the chip publishes no fp32 peak."""
    if layer["compressed"]:
        return peaks["int8_ops_per_s"]
    return peaks["bf16_flops_per_s"]


def least_time_s(layer: dict, batch: int, peaks: dict) -> float:
    """The roofline: the larger of ops over peak and bytes over bandwidth."""
    return max(batch * ops_per_image(layer) / peak_ops(layer, peaks),
               bytes_per_call(layer, batch) / peaks["hbm_bytes_per_s"])


def peak_time_per_image_s(config: dict, peaks: dict) -> float:
    """Σ over layers of ops per image over that layer's peak: the time one
    image takes at the chip's peak (the denominator of ``mfu``)."""
    return sum(ops_per_image(l) / peak_ops(l, peaks) for l in layers(config))
