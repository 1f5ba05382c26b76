"""On-chip benchmark: one cell of ``BENCHMARK.json``, one seed, one window.

    python3 benchmarks/chip/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Run from the root of a checkout, on a machine that holds the chips the
cell asks for. Everything a cell is made of is found by name:

- the cell (``workloads``) names a configuration and a traffic mix;
- the configuration's ``file`` (``configs/<name>.json``) holds its sizes
  and names its ``family`` (``families/<family>.py``: how the system
  under test is built and fed) and its plain ``reference``
  (``references/<reference>.py``: weights from the seed, float32 forward,
  and the lower-precision control);
- the mix is ``traffic/<traffic>.json``, read by ``traffic.py``;
- each metric is ``metrics/<name>.py`` with ``read(run)``, returning
  ``None`` where it finds nothing to read;
- the chip's peaks are ``peaks.json``, keyed by JAX's ``device_kind``.

``--trace 0`` measures the cell's end-to-end metrics; ``--trace 1``
records a profiler trace of the window and reports the per-layer metrics.
After the window the server stops, ``memory_peak_bytes`` is read, and
the reference checks the sampled answers (``check.py``). The last line
printed is one JSON object; the numbers compared, each with its limit,
are the last lines on standard error and the last key of that object.
Without a TPU, with fewer chips than the cell asks for, or on a chip
whose ``device_kind`` the peaks table lacks, it prints no result and
exits 1.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import contextlib  # noqa: E402
import gc  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import pathlib  # noqa: E402
import sys  # noqa: E402
import types  # noqa: E402

BENCH = pathlib.Path(__file__).resolve().parent
ROOT = BENCH.parents[1]
# fixed and inside the checkout: the path is part of the cache's key. Set
# before anything imports JAX, and handed to the program (whose own
# use_compile_cache() takes JAX_COMPILATION_CACHE_DIR where it is set)
CACHE_DIR = ROOT / ".cache" / "jax"
# reference logits are computed this many images at a time
REF_BLOCK = 128


class Refused(Exception):
    """The run cannot be made here; no result is printed."""


def load(path: pathlib.Path, name: str):
    """Import ``path`` as module ``name`` (metric files have dots in their names)."""
    if not path.is_file():
        raise Refused(f"missing {path.relative_to(ROOT)}")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def read_json(path: pathlib.Path) -> dict:
    if not path.is_file():
        raise Refused(f"missing {path.relative_to(ROOT)}")
    return json.loads(path.read_text())


def cell_of(spec: dict, name: str) -> tuple:
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise Refused(f"no workload {name!r} in BENCHMARK.json")
    cell = cells[name]
    config = {c["name"]: c for c in spec["configs"]}[cell["config"]]
    return cell, config


def metrics_for(spec: dict, cell: str, kind: str) -> list:
    return [m for m in spec[kind] if cell in m.get("workloads", [cell])]


def device_info(jax, chips: int, peaks: dict):
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise Refused(f"no TPU: JAX's devices are {sorted({d.platform for d in devs})}")
    if len(devs) < chips:
        raise Refused(f"the cell asks for {chips} chips, JAX has {len(devs)}")
    kind = devs[0].device_kind
    if kind not in peaks:
        raise Refused(f"device_kind {kind!r} is not in peaks.json")
    return devs[:chips], peaks[kind]


def seed_key(jax, seed: int):
    """A PRNG key from any whole-number seed, however large."""
    import numpy as np

    words = np.random.SeedSequence(seed).generate_state(2, dtype=np.uint32)
    return jax.random.wrap_key_data(jax.numpy.asarray(words))


def finite(v):
    return v if not isinstance(v, float) or math.isfinite(v) else None


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(CACHE_DIR)
    try:
        return measure(args)
    except Refused as e:
        print(f"[bench] refused: {e}", file=sys.stderr, flush=True)
        return 1


def measure(args, *, find_devices=None, resize=None) -> int:
    """One run. ``find_devices(jax, chips, peaks)`` and ``resize(config,
    traffic)`` let a rehearsal off the chip skip the look for a TPU and
    shrink the cell; the benchmark's own runs use neither."""
    spec = read_json(ROOT / "BENCHMARK.json")
    cell, cfg_entry = cell_of(spec, args.workload)
    config = read_json(ROOT / cfg_entry["file"])
    traffic = read_json(BENCH / "traffic" / f"{cell['traffic']}.json")
    if resize is not None:
        config, traffic = resize(config, traffic)
    peaks_table = read_json(BENCH / "peaks.json")
    family = load(BENCH / "families" / f"{config['family']}.py", "bench_family")
    reference = load(BENCH / "references" / f"{config['reference']}.py", "bench_reference")
    if not (ROOT / "src").is_dir():
        raise Refused("the system under test (src/) is not in this checkout")
    sys.path.insert(0, str(ROOT / "src"))
    sys.path[:0] = [str(BENCH), str(BENCH / "metrics")]
    import jax

    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    import numpy as np

    import check
    import devtrace as trace_mod
    import traffic as traffic_mod

    devices, peaks = (find_devices or device_info)(jax, cell["chips"], peaks_table)
    log = lambda msg: print(f"[bench] {msg}", file=sys.stderr, flush=True)  # noqa: E731
    log(f"{args.workload}: {devices[0].device_kind} x{len(devices)}, seed {args.seed}")

    k_w, k_cal, k_pool = jax.random.split(seed_key(jax, args.seed), 3)
    keys = {"weights": k_w, "calibration": k_cal}
    phases = {"start": time.perf_counter() - T_START}
    system = family.build(config, traffic, keys, devices, reference, phases)
    t = time.perf_counter()
    pool = family.make_inputs(config, k_pool, traffic["pool_images"])
    phases["pool"] = time.perf_counter() - t
    log("set-up: " + ", ".join(f"{k} {v:.2f}s" for k, v in phases.items()))
    tracer = trace_mod.Tracer() if args.trace else None
    try:
        if tracer:
            tracer.start()
        setup_s = time.perf_counter() - T_START
        with (tracer.span("bench.window") if tracer else contextlib.nullcontext()):
            win = traffic_mod.run(system, traffic, pool, args.seconds, args.seed,
                                  annotate=tracer.span if tracer else None)
        if tracer:
            tracer.stop()
        stats = system.stats
        retraces = system.retraces_after_warmup
        health = system.health()
    finally:
        system.stop()
    print(f"[bench] retraces_after_warmup {retraces}, health {health['status']}, "
          f"batches {stats.batches}, buckets {dict(sorted(stats.bucket_counts.items()))}",
          flush=True)
    mem = [d.memory_stats() or {} for d in devices]
    memory_peak = max(m.get("peak_bytes_in_use", 0) for m in mem)
    reduced = tracer.reduce() if tracer else None
    del system
    gc.collect()

    # the reference, once the window has closed and the server is gone
    t_ref = time.perf_counter()
    weights = jax.jit(lambda k: reference.init_weights(config, k))(k_w)
    ref = np.concatenate([
        np.asarray(reference.forward(config, weights, pool[i:i + REF_BLOCK]))
        for i in range(0, len(pool), REF_BLOCK)])
    correct, checks = check.decide(win, ref, config["limits"])
    log(f"reference and check took {time.perf_counter() - t_ref:.1f}s")

    # what a metric reader sees of the run
    run = types.SimpleNamespace(config=config, traffic=traffic, cell=cell,
                                chips=len(devices), peaks=peaks, window=win,
                                stats=stats, setup_s=setup_s, trace=reduced)
    kind = "per_layer" if args.trace else "end_to_end"
    metrics = {}
    for m in metrics_for(spec, args.workload, kind):
        reader = load(BENCH / "metrics" / f"{m['name']}.py", f"bench_metric_{m['name']}")
        value = reader.read(run)
        if value is not None and math.isfinite(value):
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    device = {"platform": devices[0].platform, "kind": devices[0].device_kind,
              "count": len(devices), "memory_peak_bytes": memory_peak}
    out = {"correct": correct, "attempted": len(win.n),
           "failed": len(win.error), "metrics": metrics, "device": device}
    if reduced is not None:
        device["busy_s"] = trace_mod.busy_s(reduced)
        device["window_s"] = trace_mod.window_s(reduced)
        out["breakdown"] = {"device_ops": trace_mod.device_ops(reduced),
                            "idle_gaps": trace_mod.idle_gaps(reduced)}
    out["retraces_after_warmup"] = retraces
    out["checks"] = {k: {"value": finite(v["value"]), "limit": v["limit"]}
                     for k, v in checks.items()}
    for name, c in out["checks"].items():
        log(f"check {name}: {c['value']} (limit {c['limit']})")
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
