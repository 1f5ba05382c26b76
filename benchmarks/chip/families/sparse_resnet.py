"""The sparse ResNet served the way ``launch/serve.py --server`` serves it.

``SparseResNet.compress`` → calibration → ``SparseResNet.quantize`` →
``SparseResNet.plan_set`` → ``CNNServer`` under ``Supervisor``, warmed on
the cell's own buckets. The weights come from the benchmark's reference
module (drawn from the seed on the device, in one jitted call; the system
compresses them in another). The activation scales come from one jitted
float32 pass of the system's own compressed model on its reference path
(``kernel_mode="ref"``: decode + XLA conv): ``calibration_maxima``, the
per-layer input maxima (and each projection's output maximum) that
``apply(collect_act_stats=True)`` would record, go to the system's own
``quantize``.
"""
from __future__ import annotations

import time
import types

import jax
import jax.numpy as jnp
import numpy as np


def _resnet_config(config: dict, kernel_mode: str = "pallas"):
    from repro.core.vdbb import DBBFormat
    from repro.models.resnet import ResNetConfig

    d = config["dbb"]
    widths = tuple(config["stage_channels"])
    return ResNetConfig(
        name=config["name"], in_channels=config["in_channels"],
        image_size=config["image_size"], stem_channels=config["stem_channels"],
        stem_kernel=config["stem_kernel"], stage_widths=widths,
        stage_blocks=tuple(config["stage_blocks"][:len(widths)]),
        expansion=config["expansion"], num_classes=config["num_classes"],
        dbb=DBBFormat(d["bz"], d["nnz"], d["group"]), dtype=jnp.float32,
        kernel_mode=kernel_mode)


def _program_params(model, weights):
    """The reference's {name: (w, b)} in the system's {name: {"w", "b"}}
    tree, compressed by the system."""
    return model.compress({name: {"w": w, "b": b} for name, (w, b) in weights.items()})


def build(config: dict, traffic: dict, keys: dict, devices: list, reference,
          phases: dict):
    """Build, calibrate, plan, start and warm the server for one cell;
    ``phases`` gets the seconds each step of set-up took. Returns the
    started ``Supervisor``."""
    from repro.launch.server import CNNServer
    from repro.launch.supervisor import Supervisor
    from repro.models.resnet import SparseResNet

    t = time.perf_counter()

    def lap(name):
        nonlocal t
        now = time.perf_counter()
        phases[name] = now - t
        t = now

    model = SparseResNet(_resnet_config(config))
    weights = jax.jit(lambda k: reference.init_weights(config, k))(keys["weights"])
    params = jax.block_until_ready(
        jax.jit(lambda w: _program_params(model, w))(weights))
    del weights
    lap("weights")
    shape = (config["image_size"], config["image_size"], config["in_channels"])
    x_cal = jax.random.normal(keys["calibration"], (config["calibration_images"], *shape))
    ref_path = SparseResNet(_resnet_config(config, "ref"))
    maxima = jax.device_get(jax.jit(ref_path.calibration_maxima)(params, x_cal))
    lap("calibration")
    qparams = jax.block_until_ready(model.quantize(
        params, {k: types.SimpleNamespace(absmax=float(v)) for k, v in maxima.items()}))
    lap("quantize")
    plan_set = model.plan_set(qparams, buckets=traffic["buckets"], tune="cache")
    lap("plan_set")
    srv = CNNServer(plan_set, max_wait_ms=traffic["max_wait_ms"])
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
