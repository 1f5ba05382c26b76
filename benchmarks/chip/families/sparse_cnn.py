"""The sparse CNN served the way ``launch/serve.py --server`` serves it.

``SparseCNN.compress`` → calibration → ``SparseCNN.quantize`` →
``SparseCNN.plan_set`` → ``CNNServer`` under ``Supervisor``, warmed on the
cell's own buckets. The weights come from the benchmark's reference module
(drawn from the seed on the device, in one jitted call; the system
compresses them in another: fused, the two take the TPU compiler three
times as long). The activation scales come from one jitted float32
forward pass of the system's own compressed model on its reference path
(``kernel_mode="ref"``: decode + XLA conv, which compiles in a sixth of the
Pallas path's time), whose per-layer input maxima go to the system's own
``quantize`` (what ``apply(collect_act_stats=True)`` would record, without
running the model op by op).
"""
from __future__ import annotations

import time
import types

import jax
import jax.numpy as jnp
import numpy as np


def _cnn_config(config: dict, kernel_mode: str = "pallas"):
    from repro.core.vdbb import DBBFormat
    from repro.models.cnn import CNNConfig

    d = config["dbb"]
    return CNNConfig(
        name=config["name"], in_channels=config["in_channels"],
        image_size=config["image_size"],
        stage_channels=tuple(config["stage_channels"]),
        convs_per_stage=config["convs_per_stage"],
        kernel_size=config["kernel_size"], num_classes=config["num_classes"],
        dbb=DBBFormat(d["bz"], d["nnz"], d["group"]), dtype=jnp.float32,
        kernel_mode=kernel_mode)


def _program_params(model, weights):
    """The reference's [(w, b)] in the system's {"l<i>": {"w", "b"}} tree,
    compressed by the system."""
    return model.compress({f"l{i}": {"w": w, "b": b}
                           for i, (w, b) in enumerate(weights)})


def _input_maxima(model, params, x):
    """Largest |input| of every layer: the stem's input, each conv's ReLU
    output, and the pooled vector the head reads."""
    inter = []
    model.apply(params, x, intermediates=inter)
    ins = [x, *inter[:-1], inter[-1].mean(axis=(1, 2))]
    return jnp.stack([jnp.max(jnp.abs(a)) for a in ins])


def build(config: dict, traffic: dict, keys: dict, devices: list, reference,
          phases: dict):
    """Build, calibrate, plan, start and warm the server for one cell;
    ``phases`` gets the seconds each step of set-up took. Returns the
    started ``Supervisor``: ``submit``, ``stats``, ``retraces_after_warmup``,
    ``health()``, ``stop()``."""
    from repro.launch.server import CNNServer
    from repro.launch.supervisor import Supervisor
    from repro.models.cnn import SparseCNN

    t = time.perf_counter()

    def lap(name):
        nonlocal t
        now = time.perf_counter()
        phases[name] = now - t
        t = now

    model = SparseCNN(_cnn_config(config))
    weights = jax.jit(lambda k: reference.init_weights(config, k))(keys["weights"])
    params = jax.block_until_ready(
        jax.jit(lambda w: _program_params(model, w))(weights))
    del weights
    lap("weights")
    shape = (config["image_size"], config["image_size"], config["in_channels"])
    x_cal = jax.random.normal(keys["calibration"], (config["calibration_images"], *shape))
    ref_path = SparseCNN(_cnn_config(config, "ref"))
    maxima = np.asarray(jax.jit(lambda p, x: _input_maxima(ref_path, p, x))(params, x_cal))
    lap("calibration")
    qparams = jax.block_until_ready(model.quantize(
        params, [types.SimpleNamespace(absmax=float(a)) for a in maxima]))
    lap("quantize")
    dp = int(traffic.get("dp", 1))
    plan_set = model.plan_set(qparams, buckets=traffic["buckets"], dp=dp,
                              tune="cache")
    lap("plan_set")
    mesh = None
    if dp > 1:
        from repro.launch.mesh import auto_mesh

        mesh = auto_mesh((dp, 1), ("data", "model"), devices=devices[:dp])
    srv = CNNServer(plan_set, max_wait_ms=traffic["max_wait_ms"], mesh=mesh)
    sup = Supervisor(srv).start()
    try:
        sup.warmup()
    except BaseException:
        sup.stop()
        raise
    lap("warmup")
    return sup


def make_inputs(config: dict, key, n: int) -> np.ndarray:
    """``n`` request images (host float32), drawn on the device."""
    shape = (n, config["image_size"], config["image_size"], config["in_channels"])
    return np.asarray(jax.jit(lambda k: jax.random.normal(k, shape))(key))
