"""Plain float32 reference of the sparse VGG-style CNN, and its weights.

The model: a stack of 3×3 convs (SAME padding, ReLU), the first conv of
every stage after the first with stride 2, global average pooling, and a
linear head. Weights of every layer whose input channels divide into
blocks of ``bz`` keep at most ``nnz`` non-zeros in each block of ``bz``
along the reduction K = kh·kw·C ("DBB", arXiv 2009.02381 §II); with
``group: "matrix"`` one pattern per block is shared by every output
column. The first conv (C = 3) is dense.

It imports nothing of the system under test: the benchmark makes the
weights here from the seed, hands them to the system, and compares what
the system serves with :func:`forward` at ``highest`` matmul precision.
:func:`forward` with ``bits`` set is the control: the same model with the
compressed layers' weights (per output channel) and input activations
(per tensor, scaled from :func:`calibrate`) rounded to ``bits``-bit
integers and the dense stem computed in bfloat16.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST


def conv_strides(config: dict) -> list:
    """(cin, cout, stride) of every conv, in order."""
    out, cin = [], config["in_channels"]
    for si, ch in enumerate(config["stage_channels"]):
        for li in range(config["convs_per_stage"]):
            out.append((cin, ch, 2 if (si > 0 and li == 0) else 1))
            cin = ch
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


def init_weights(config: dict, key) -> list:
    """[(w, b), ...] for every conv ((kh, kw, C, F) HWIO) and the head
    ((C, classes)): He-scaled over the kept fan-in, small random biases."""
    dbb, ks = config["dbb"], config["kernel_size"]
    if dbb["group"] not in ("matrix", None):
        raise ValueError(f"pattern group {dbb['group']!r} is not drawn here")
    shapes = [(ks * ks * cin, cout, True) for cin, cout, _ in conv_strides(config)]
    shapes.append((config["stage_channels"][-1], config["num_classes"], False))
    out = []
    for (k, n, relu), lk in zip(shapes, jax.random.split(key, len(shapes))):
        kw_, km, kb = jax.random.split(lk, 3)
        sparse = (k // (ks * ks if relu else 1)) % dbb["bz"] == 0
        density = dbb["nnz"] / dbb["bz"] if sparse else 1.0
        w = jax.random.normal(kw_, (k, n)) * jnp.sqrt((2.0 if relu else 1.0) / (k * density))
        if sparse:
            w = w * _dbb_mask(km, k, n, dbb)
        b = 0.1 * jax.random.normal(kb, (n,))
        if relu:
            w = w.reshape(ks, ks, k // (ks * ks), n)
        out.append((w, b))
    return out


def _fake_quant(x, scale, bits: int):
    qmax = 2 ** (bits - 1) - 1
    return jnp.clip(jnp.round(x / scale), -qmax, qmax) * scale


def _weight_quant(w, bits: int):
    qmax = 2 ** (bits - 1) - 1
    amax = jnp.max(jnp.abs(w.reshape(-1, w.shape[-1])), axis=0)
    return _fake_quant(w, jnp.maximum(amax, 1e-12) / qmax, bits)


def _layer_inputs(config: dict, weights: list, x, *, bits=None, amax=None):
    """The input of every layer (convs, then the head) and the logits."""
    dbb = config["dbb"]
    qmax = None if bits is None else 2 ** (bits - 1) - 1
    ins = []
    h = x
    for i, ((cin, _, stride), (w, b)) in enumerate(zip(conv_strides(config), weights)):
        ins.append(h)
        if bits is not None and cin % dbb["bz"] == 0:
            h, w = _fake_quant(h, amax[i] / qmax, bits), _weight_quant(w, bits)
        if bits is not None and cin % dbb["bz"]:  # the dense stem, in bf16
            y = jax.lax.conv_general_dilated(
                h.astype(jnp.bfloat16), w.astype(jnp.bfloat16), (stride, stride),
                "SAME", dimension_numbers=("NHWC", "HWIO", "NHWC"),
                preferred_element_type=jnp.float32)
        else:
            y = jax.lax.conv_general_dilated(
                h, w, (stride, stride), "SAME",
                dimension_numbers=("NHWC", "HWIO", "NHWC"), precision=HIGHEST)
        h = jax.nn.relu(y + b)
    h = h.mean(axis=(1, 2))
    ins.append(h)
    w, b = weights[-1]
    if bits is not None:
        h, w = _fake_quant(h, amax[-1] / qmax, bits), _weight_quant(w, bits)
    return ins, jnp.matmul(h, w, precision=HIGHEST) + b


def _key(config: dict) -> tuple:
    """The sizes the forward pass reads, as a hashable jit key."""
    d = config["dbb"]
    return (config["in_channels"], tuple(config["stage_channels"]),
            config["convs_per_stage"], config["kernel_size"],
            config["num_classes"], (d["bz"], d["nnz"], d["group"]))


def _config(key: tuple) -> dict:
    cin, stages, per, ks, classes, (bz, nnz, group) = key
    return dict(in_channels=cin, stage_channels=stages, convs_per_stage=per,
                kernel_size=ks, num_classes=classes,
                dbb=dict(bz=bz, nnz=nnz, group=group))


@functools.partial(jax.jit, static_argnums=0)
def _calibrate(key, weights, x):
    ins, _ = _layer_inputs(_config(key), weights, x)
    return jnp.stack([jnp.max(jnp.abs(a)) for a in ins])


@functools.partial(jax.jit, static_argnums=(0, 3))
def _forward(key, weights, x, bits, amax):
    return _layer_inputs(_config(key), weights, x, bits=bits, amax=amax)[1]


def calibrate(config: dict, weights: list, x) -> jax.Array:
    """Largest |input| of every layer over ``x`` (the control's scales)."""
    return _calibrate(_key(config), weights, x)


def forward(config: dict, weights: list, x, *, bits=None, amax=None) -> jax.Array:
    """Logits of ``x`` (N, H, W, C). ``bits`` with ``amax`` from
    :func:`calibrate` gives the lower-precision control."""
    if (bits is None) != (amax is None):
        raise ValueError("the control needs both bits and amax")
    return _forward(_key(config), weights, x, bits, amax)
