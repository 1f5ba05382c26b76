"""Plain float32 reference of ResNet-50 v1.5 with VDBB weights, and its weights.

The model (He et al., arXiv:1512.03385, Table 1, the 50-layer column; the
v1.5 form strides the 3×3 conv of a downsampling block): a 7×7/2 stem
(padding 3) with ReLU, a 3×3/2 max-pool (padding 1), stages of
bottleneck blocks — 1×1 (ReLU), 3×3 (ReLU; stride 2 in the first block of
every stage after the first; padding 1), 1×1 — whose output is
``relu(branch + shortcut)``, the shortcut a 1×1 projection (with the
block's stride) in each stage's first block and the identity elsewhere;
global average pooling and a linear head. BatchNorm is folded into each
conv's weight and bias. Every layer whose input channels divide into
blocks of ``bz`` keeps at most ``nnz`` non-zeros in each block of ``bz``
along the reduction K = kh·kw·C, one pattern per block shared by all
output columns (``group: "matrix"``); the stem (C = 3) is dense.

A configuration may list fewer ``stage_channels`` (the bottleneck widths)
than ``stage_blocks``: the stages are those it lists (a smoke size).

Random weights need one choice a trained network makes for itself: each
block's last 1×1 conv is drawn at the gain ``config["residual_gain"]``
(a fraction of its He scale). At full gain, the sixteen identity-summed
branches grow the residual stream until per-tensor int8 calibration
spends its range on a few outliers, and the comparison would measure the
draw, not the system.

It imports nothing of the system under test: the benchmark makes the
weights here from the seed, hands them to the system (``{name: (w, b)}``
under the system's layer names), and compares what the system serves with
:func:`forward` at ``highest`` matmul precision. :func:`forward` with
``bits`` set is the control: the compressed layers' weights (per output
channel) and input activations (per tensor, scaled from :func:`calibrate`)
rounded to ``bits``-bit integers and the dense stem computed in bfloat16.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST
POINTWISE = ((0, 0), (0, 0))
PAD1 = ((1, 1), (1, 1))


def layers(config: dict) -> list:
    """(name, k, cin, cout, stride, padding) of every conv in forward order
    (a block's c1, c2, c3, then its projection), then the head."""
    pad = config["stem_kernel"] // 2
    out = [("stem", config["stem_kernel"], config["in_channels"],
            config["stem_channels"], 2, ((pad, pad), (pad, pad)))]
    cin = config["stem_channels"]
    widths = config["stage_channels"]
    for si, (width, n) in enumerate(zip(widths, config["stage_blocks"][:len(widths)])):
        cout = width * config["expansion"]
        for bi in range(n):
            name, stride = f"s{si + 1}b{bi + 1}", 2 if (si > 0 and bi == 0) else 1
            out += [(f"{name}.c1", 1, cin, width, 1, POINTWISE),
                    (f"{name}.c2", 3, width, width, stride, PAD1),
                    (f"{name}.c3", 1, width, cout, 1, POINTWISE)]
            if bi == 0:
                out.append((f"{name}.proj", 1, cin, cout, stride, POINTWISE))
            cin = cout
    out.append(("fc", 1, cin, config["num_classes"], 1, None))
    return out


def _dbb_mask(key, k: int, n: int, dbb: dict) -> jax.Array:
    """(K, N) mask: ``nnz`` kept positions in every block of ``bz`` along K,
    shared by all N columns (``group: "matrix"``) or drawn per column."""
    bz, nnz = dbb["bz"], dbb["nnz"]
    cols = 1 if dbb["group"] == "matrix" else n
    order = jnp.argsort(jax.random.uniform(key, (k // bz, cols, bz)), axis=-1)
    keep = jax.nn.one_hot(order[..., :nnz], bz).sum(axis=-2)  # (nb, cols, bz)
    mask = keep.transpose(0, 2, 1).reshape(k, cols)
    return jnp.broadcast_to(mask, (k, n))


def init_weights(config: dict, key) -> dict:
    """``{name: (w, b)}`` for every conv ((kh, kw, C, F) HWIO) and the head
    ((C, classes)): He-scaled over the kept fan-in (gain 2 before a ReLU,
    1 for a projection and the head, ``residual_gain`` for a block's c3),
    small random biases (BatchNorm folded)."""
    dbb = config["dbb"]
    if dbb["group"] not in ("matrix", None):
        raise ValueError(f"pattern group {dbb['group']!r} is not drawn here")
    specs = layers(config)
    out = {}
    for (name, ks, cin, cout, _, _), lk in zip(specs, jax.random.split(key, len(specs))):
        kw_, km, kb = jax.random.split(lk, 3)
        k = ks * ks * cin
        sparse = cin % dbb["bz"] == 0
        density = dbb["nnz"] / dbb["bz"] if sparse else 1.0
        gain = (config["residual_gain"] if name.endswith(".c3")
                else 1.0 if name.endswith(".proj") or name == "fc" else 2.0)
        w = jax.random.normal(kw_, (k, cout)) * jnp.sqrt(gain / (k * density))
        if sparse:
            w = w * _dbb_mask(km, k, cout, dbb)
        b = 0.1 * jax.random.normal(kb, (cout,))
        out[name] = (w if name == "fc" else w.reshape(ks, ks, cin, cout), b)
    return out


def _fake_quant(x, scale, bits: int):
    qmax = 2 ** (bits - 1) - 1
    return jnp.clip(jnp.round(x / scale), -qmax, qmax) * scale


def _weight_quant(w, bits: int):
    qmax = 2 ** (bits - 1) - 1
    amax = jnp.max(jnp.abs(w.reshape(-1, w.shape[-1])), axis=0)
    return _fake_quant(w, jnp.maximum(amax, 1e-12) / qmax, bits)


def max_pool(x):
    return jax.lax.reduce_window(x, -jnp.inf, jax.lax.max, (1, 3, 3, 1),
                                 (1, 2, 2, 1), ((0, 0), *PAD1, (0, 0)))


def _layer_inputs(config: dict, weights: dict, x, *, bits=None, amax=None):
    """The input of every layer (in :func:`layers` order) and the logits."""
    specs = layers(config)
    index = {s[0]: i for i, s in enumerate(specs)}
    qmax = None if bits is None else 2 ** (bits - 1) - 1
    ins = [None] * len(specs)

    def conv(name, h):
        _, _, cin, _, stride, padding = specs[index[name]]
        ins[index[name]] = h
        w, b = weights[name]
        if bits is not None and cin % config["dbb"]["bz"] == 0:
            h, w = _fake_quant(h, amax[index[name]] / qmax, bits), _weight_quant(w, bits)
        if bits is not None and cin % config["dbb"]["bz"]:  # the dense stem, in bf16
            y = jax.lax.conv_general_dilated(
                h.astype(jnp.bfloat16), w.astype(jnp.bfloat16), (stride, stride),
                padding, dimension_numbers=("NHWC", "HWIO", "NHWC"),
                preferred_element_type=jnp.float32)
        else:
            y = jax.lax.conv_general_dilated(
                h, w, (stride, stride), padding,
                dimension_numbers=("NHWC", "HWIO", "NHWC"), precision=HIGHEST)
        return y + b

    h = max_pool(jax.nn.relu(conv("stem", x)))
    blocks = [s[0][:-3] for s in specs if s[0].endswith(".c1")]
    for name in blocks:
        y = jax.nn.relu(conv(f"{name}.c1", h))
        y = jax.nn.relu(conv(f"{name}.c2", y))
        y = conv(f"{name}.c3", y)
        short = conv(f"{name}.proj", h) if f"{name}.proj" in index else h
        h = jax.nn.relu(y + short)
    h = h.mean(axis=(1, 2))
    ins[-1] = h
    w, b = weights["fc"]
    if bits is not None:
        h, w = _fake_quant(h, amax[-1] / qmax, bits), _weight_quant(w, bits)
    return ins, jnp.matmul(h, w, precision=HIGHEST) + b


def _key(config: dict) -> tuple:
    """The sizes the forward pass reads, as a hashable jit key."""
    d = config["dbb"]
    return (config["in_channels"], config["stem_channels"], config["stem_kernel"],
            tuple(config["stage_channels"]), tuple(config["stage_blocks"]),
            config["expansion"], config["num_classes"], (d["bz"], d["nnz"], d["group"]))


def _config(key: tuple) -> dict:
    cin, stem, sk, widths, blocks, exp, classes, (bz, nnz, group) = key
    return dict(in_channels=cin, stem_channels=stem, stem_kernel=sk,
                stage_channels=widths, stage_blocks=blocks, expansion=exp,
                num_classes=classes, dbb=dict(bz=bz, nnz=nnz, group=group))


@functools.partial(jax.jit, static_argnums=0)
def _calibrate(key, weights, x):
    ins, _ = _layer_inputs(_config(key), weights, x)
    return jnp.stack([jnp.max(jnp.abs(a)) for a in ins])


@functools.partial(jax.jit, static_argnums=(0, 3))
def _forward(key, weights, x, bits, amax):
    return _layer_inputs(_config(key), weights, x, bits=bits, amax=amax)[1]


def calibrate(config: dict, weights: dict, x) -> jax.Array:
    """Largest |input| of every layer over ``x``, in :func:`layers` order
    (the control's scales)."""
    return _calibrate(_key(config), weights, x)


def forward(config: dict, weights: dict, x, *, bits=None, amax=None) -> jax.Array:
    """Logits of ``x`` (N, H, W, C). ``bits`` with ``amax`` from
    :func:`calibrate` gives the lower-precision control."""
    if (bits is None) != (amax is None):
        raise ValueError("the control needs both bits and amax")
    return _forward(_key(config), weights, x, bits, amax)
