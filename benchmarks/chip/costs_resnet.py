"""Operations and bytes of each served layer of a bottleneck ResNet, from
its shapes alone, on the yardstick of ``costs.py``: a compressed layer
counts only the MACs of the non-zeros it keeps, and bytes are each operand
once — the raw input activation, the compressed weight values and their
position indices, the per-channel scale and bias rows, the output, and
for a block's closing 1×1 conv the residual it reads (the shortcut's int8
codes, an input stream of that call).

Operand widths on the served int8 chain: the stem reads fp32 images and
writes int8 codes (the max-pool moves no MACs and is not a layer here);
every compressed conv reads and writes int8 except the last block's c3,
which flushes fp32 into global average pooling; a projection writes int8
codes, which its block's c3 reads; the head reads the pooled vector's
int8 codes and writes fp32 logits.
"""
from __future__ import annotations

import re

import costs
from costs import F32, I8

_SHAPE = re.compile(r"[a-z]+\d*\[([\d,]*)\]")


def _out(h: int, k: int, stride: int, pad: int) -> int:
    return (h + 2 * pad - k) // stride + 1


def layers(config: dict) -> list:
    """One dict per served layer, in order: ``stem``, each block's
    ``conv1x1`` (c1, c3, proj) and ``conv3x3`` (c2), ``head``. Keys as in
    ``costs.layers`` plus ``res_bytes`` (bytes per output element of the
    residual a c3 reads, else 0)."""
    dbb = config["dbb"]
    bz, nnz, group = dbb["bz"], dbb["nnz"], dbb["group"]
    sk = config["stem_kernel"]
    h = config["image_size"]
    ho = _out(h, sk, 2, sk // 2)
    out = [dict(name="stem", kind="stem", h=h, w=h, cin=config["in_channels"],
                cout=config["stem_channels"], k=sk, stride=2, ho=ho, wo=ho,
                in_bytes=F32, out_bytes=I8, res_bytes=0)]
    h, cin = _out(ho, 3, 2, 1), config["stem_channels"]  # the max-pool
    widths = config["stage_channels"]
    blocks = [(si, bi, width) for si, (width, n) in
              enumerate(zip(widths, config["stage_blocks"][:len(widths)]))
              for bi in range(n)]
    for i, (si, bi, width) in enumerate(blocks):
        name, stride = f"s{si + 1}b{bi + 1}", 2 if (si > 0 and bi == 0) else 1
        cout, hs = width * config["expansion"], _out(h, 3, stride, 1)
        last = i == len(blocks) - 1

        def conv(part, kind, k, c, f, hi, hj, out_bytes=I8, res_bytes=0):
            out.append(dict(name=f"{name}.{part}", kind=kind, h=hi, w=hi, cin=c,
                            cout=f, k=k, stride=1 if hj == hi else 2, ho=hj,
                            wo=hj, in_bytes=I8, out_bytes=out_bytes,
                            res_bytes=res_bytes))

        conv("c1", "conv1x1", 1, cin, width, h, h)
        conv("c2", "conv3x3", 3, width, width, h, hs)
        conv("c3", "conv1x1", 1, width, cout, hs, hs,
             out_bytes=F32 if last else I8, res_bytes=I8)
        if bi == 0:
            conv("proj", "conv1x1", 1, cin, cout, h, hs)
        h, cin = hs, cout
    out.append(dict(name="fc", kind="head", h=1, w=1, cin=cin,
                    cout=config["num_classes"], k=1, stride=1, ho=1, wo=1,
                    in_bytes=I8, out_bytes=F32, res_bytes=0))
    for layer in out:
        compressed = layer["cin"] % bz == 0
        layer.update(compressed=compressed, bz=bz, nnz=nnz if compressed else bz,
                     group=group)
    return out


def ops_per_image(layer: dict) -> int:
    return costs.ops_per_image(layer)


def bytes_per_call(layer: dict, batch: int) -> int:
    """``costs.bytes_per_call`` plus the residual's int8 stream of a c3."""
    res = batch * layer["ho"] * layer["wo"] * layer["cout"] * layer["res_bytes"]
    return costs.bytes_per_call(layer, batch) + res


def least_time_s(layer: dict, batch: int, peaks: dict) -> float:
    """The roofline: the larger of ops over peak and bytes over bandwidth."""
    return max(batch * ops_per_image(layer) / costs.peak_ops(layer, peaks),
               bytes_per_call(layer, batch) / peaks["hbm_bytes_per_s"])


def peak_time_per_image_s(config: dict, peaks: dict) -> float:
    """Σ over layers of ops per image over that layer's peak (int8 for the
    compressed layers, bf16 for the fp32 stem): the time one image takes
    at the chip's peak (the denominator of ``mfu.resnet``)."""
    return sum(ops_per_image(l) / costs.peak_ops(l, peaks) for l in layers(config))


def call_shapes(event_name: str):
    """(output dims, [operand dims, ...]) of a trace event that is a
    Pallas kernel, else None. The event name is the HLO instruction:
    ``%x = s8[N,H,W,F]{...} custom-call(s8[...] %a, s8[...] %b, ...), ...``."""
    if 'custom_call_target="tpu_custom_call"' not in event_name:
        return None
    lhs, _, rhs = event_name.partition(" custom-call(")
    args = rhs.partition("), custom_call_target=")[0]
    dims = lambda s: tuple(int(d) for d in s.split(",") if d)  # noqa: E731
    out = _SHAPE.search(lhs)
    return (dims(out.group(1)) if out else ()), [dims(m) for m in _SHAPE.findall(args)]


def match_conv(event_name: str, convs: list):
    """``(layer, batch)`` of the compressed conv a kernel call runs, else
    None: its output (N, Ho, Wo, F), its input's channels C (the last dim
    of the first operand) and its weight operand (kh·kw, C·nnz/bz, F) —
    the taps tell a 1×1 from a 3×3 where output and input agree."""
    call = call_shapes(event_name)
    if call is None:
        return None
    out, ops = call
    if len(out) != 4 or len(ops) < 2 or not ops[0]:
        return None
    for layer in convs:
        weight = (layer["k"] ** 2, layer["cin"] // layer["bz"] * layer["nnz"], layer["cout"])
        if (tuple(out[1:]) == (layer["ho"], layer["wo"], layer["cout"])
                and ops[0][-1] == layer["cin"] and tuple(ops[1]) == weight):
            return layer, out[0]
    return None


def roofline_share(run, kinds) -> float | None:
    """Σ least time ÷ Σ device time, in %, over the traced window's
    compressed-conv calls of the given layer ``kinds``; None where none
    ran (or no trace was taken)."""
    import devtrace

    if run.trace is None:
        return None
    convs = [l for l in layers(run.config) if l["kind"] in kinds and l["compressed"]]
    least = spent = 0.0
    for ev in run.trace["devices"].values():
        for name, _, dur in devtrace.in_window(run.trace, ev):
            hit = match_conv(name, convs)
            if hit is not None:
                least += least_time_s(hit[0], hit[1], run.peaks)
                spent += dur / 1e9
    return 100.0 * least / spent if spent else None
