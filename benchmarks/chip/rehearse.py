"""Rehearse the benchmark without a chip.

    JAX_PLATFORMS=cpu python3 benchmarks/chip/rehearse.py [--run] [--compile]

``--run``: every cell of ``BENCHMARK.json`` end to end on the CPU at a
smoke size (images 16×16, two stages, small buckets, a short window),
Pallas kernels in interpret mode, skipping only the look for a TPU. Its
numbers say nothing about speed; it shows that the harness, the traffic,
the metrics and the check run and that ``correct`` comes out true.

``--compile``: every cell's bucket programs at the real size (or those
of ``--cell CONFIG:TRAFFIC``), compiled for a described ``v5e:2x2``
(nothing runs): the one-chip cells on one chip, a mix that sets ``dp`` on
a (data=dp, model=1) mesh of that many. Prints compile seconds, Pallas kernels (``tpu_custom_call``) and
device bytes per program. Both by default.
"""
from __future__ import annotations

import argparse
import json
import os
import pathlib
import re
import sys
import time

BENCH = pathlib.Path(__file__).resolve().parent
ROOT = BENCH.parents[1]
SMOKE_SECONDS = 2.0


def smoke(config: dict, traffic: dict) -> tuple:
    """The cell at a size the CPU's kernel interpreter gets through."""
    config = dict(config, image_size=16, stage_channels=[16, 32],
                  num_classes=10, calibration_images=4)
    traffic = dict(traffic, pool_images=64)
    if traffic["loop"] == "closed":
        traffic.update(request_images=8 * traffic.get("dp", 1),
                       buckets=[8 * traffic.get("dp", 1)])
    else:
        traffic.update(rate_rps=20.0, buckets=[1, 2, 4, 8])
    return config, traffic


def cpu_devices(jax, chips: int, peaks: dict):
    devs = jax.devices()
    return devs[:chips], peaks["TPU v5 lite"]


def rehearse_runs(spec: dict) -> bool:
    import run

    ok = True
    for cell in spec["workloads"]:
        if cell["chips"] > 1 and len(__import__("jax").devices()) < cell["chips"]:
            print(f"[rehearse] {cell['name']}: skipped, needs {cell['chips']} "
                  "devices (XLA_FLAGS=--xla_force_host_platform_device_count=4)")
            continue
        args = argparse.Namespace(workload=cell["name"], seed=2**31 + 12345,
                                  seconds=SMOKE_SECONDS, trace=0)
        t0 = time.perf_counter()
        rc = run.measure(args, find_devices=cpu_devices, resize=smoke)
        print(f"[rehearse] {cell['name']}: exit {rc} in {time.perf_counter() - t0:.0f}s",
              flush=True)
        ok = ok and rc == 0
    return ok


def rehearse_compile(spec: dict, pairs: list) -> None:
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax
    import numpy as np
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import NamedSharding, PartitionSpec as P, SingleDeviceSharding

    sys.path[:0] = [str(ROOT / "src"), str(BENCH)]
    from families import sparse_cnn
    from references import sparse_cnn as reference
    from repro.launch.mesh import auto_mesh
    from repro.models.cnn import SparseCNN

    # a described chip's programs cannot be read back from the cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    backend = jax.default_backend
    jax.default_backend = lambda: "tpu"  # the kernels compile, not interpret
    try:
        for name, config_name, traffic_name in pairs:
            entry = {c["name"]: c for c in spec["configs"]}[config_name]
            config = json.loads((ROOT / entry["file"]).read_text())
            traffic = json.loads((BENCH / "traffic" / f"{traffic_name}.json").read_text())
            dp = int(traffic.get("dp", 1))
            model = SparseCNN(sparse_cnn._cnn_config(config))
            params = jax.jit(lambda k: sparse_cnn._program_params(
                model, reference.init_weights(config, k)))(jax.random.PRNGKey(0))
            unit = [type("S", (), {"absmax": 1.0})] * (len(model.layers()))
            plan_set = model.plan_set(model.quantize(params, unit),
                                      buckets=traffic["buckets"], dp=dp, tune="cache")
            if dp > 1:
                mesh = auto_mesh((dp, 1), ("data", "model"), devices=topo.devices[:dp])
                plan_set = plan_set.shard(mesh, P("data"))
                sharding = NamedSharding(mesh, P("data"))
            else:
                sharding = SingleDeviceSharding(topo.devices[0])
            shape = (config["image_size"], config["image_size"], config["in_channels"])
            for b in plan_set.buckets:
                x = jax.ShapeDtypeStruct((b, *shape), np.float32, sharding=sharding)
                t0 = time.perf_counter()
                compiled = plan_set.plans[b].lower(x).compile()
                kernels = len(re.findall(r'custom_call_target="tpu_custom_call"',
                                         compiled.as_text()))
                mem = compiled.memory_analysis()
                print(f"[rehearse] {name} bucket {b}: compiled for v5e in "
                      f"{time.perf_counter() - t0:.1f}s, {kernels} tpu_custom_call, "
                      f"temp {getattr(mem, 'temp_size_in_bytes', 'n/a')} B", flush=True)
                if not kernels:
                    raise RuntimeError(f"{name} bucket {b} holds no Pallas kernel")
    finally:
        jax.default_backend = backend


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--run", action="store_true")
    ap.add_argument("--compile", action="store_true")
    ap.add_argument("--cell", action="append", default=[], metavar="CONFIG:TRAFFIC",
                    help="compile this pairing instead of BENCHMARK.json's cells")
    args = ap.parse_args(argv)
    both = not (args.run or args.compile)
    sys.path.insert(0, str(BENCH))
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    ok = True
    if args.run or both:
        ok = rehearse_runs(spec)
    if args.compile or both:
        pairs = [(w["name"], w["config"], w["traffic"]) for w in spec["workloads"]]
        if args.cell:
            pairs = [(c, *c.split(":")) for c in args.cell]
        rehearse_compile(spec, pairs)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
