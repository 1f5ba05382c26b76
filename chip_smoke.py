"""Serve sparse-cnn-s INT8 on a TPU through the public serving path, and
check every answer.

    python chip_smoke.py              # one chip: the serving path below
    python chip_smoke.py --chips 4    # four chips: mesh data-parallel serving

One chip: ``SparseCNN.compress`` → calibrate with
``apply(collect_act_stats=True)`` → ``quantize`` →
``plan_set(max_batch=8)`` → ``CNNServer`` under ``Supervisor``,
with the Pallas kernels compiled for the chip. It serves a few dozen
single-image requests — Poisson arrivals, then a burst that fills the
largest bucket — and checks that

* the device is a TPU and the kernels are compiled, not interpreted;
* every bucket's program holds Pallas kernels (``tpu_custom_call``), the
  largest one at least one per compressed layer plus the stem;
* every request is answered: nothing fails, expires or is shed, no bucket
  is demoted, and health stays ``ready``;
* every answer is finite, of shape (1, 1000), and within ``REF_TOL``
  relative L2 of the ``kernel_mode="ref"`` plan of the same quantized
  params; and all answers together are within ``INT8_TOL`` of the fp32
  model (DESIGN.md §8).

``--chips 4`` runs only the data-parallel path: the same model on a
(data=4, model=1) mesh with ``plan_set(dp=4)``, every answer compared with
the same requests served on one device, and a check that each bucket's
rows really spread over the four devices.

Weights and inputs are random, made from ``--seed``. Everything runs in
this one process (a chip belongs to one process). The last line printed
is ``{"ok": true, "device": {"platform", "kind", "count"}}``; on any
failure the script prints why and exits non-zero without that line —
also when JAX finds no TPU, and when the repo's ``src/`` is not beside it.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import pathlib
import re
import sys
import time

ROOT = pathlib.Path(__file__).resolve().parent
# A compiled answer and the ref plan differ only where the fp32 stem and
# the fp32 epilogues round differently on the two backends; one flipped
# int8 code moves this random-weight model's logits by up to ~1% (CPU
# emulation), a wrong kernel by O(100%). The bound is the int8 budget.
REF_TOL = 0.05
INT8_TOL = 0.05  # int8 logits vs fp32, relative L2 (DESIGN.md §8)
ARCH = "sparse-cnn-s"
REQUESTS = 40  # single-image requests per run, the last burst among them


class SmokeFailure(Exception):
    pass


def check(cond, msg):
    if not cond:
        raise SmokeFailure(msg)


def log(msg):
    print(f"[chip_smoke] {msg}", flush=True)


def rel_l2(a, b):
    import numpy as np

    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return float(np.linalg.norm(a - b) / max(np.linalg.norm(b), 1e-30))


def custom_calls(compiled) -> list:
    """Result shapes of the Pallas kernels in a compiled program."""
    return [m.group(1) for m in re.finditer(
        r"= \(?(\w+\[[\d,]*\])[^\n]*custom_call_target=\"tpu_custom_call\"",
        compiled.as_text())]


def setup():
    """Import the repo's package from beside this file, place the compile
    cache, and refuse anything but compiled kernels on a TPU."""
    src = ROOT / "src"
    check((src / "repro").is_dir(), f"no repro package at {src}")
    sys.path.insert(0, str(src))
    from repro.xla_utils import use_compile_cache

    log(f"compile cache: {use_compile_cache()}")
    import jax

    from repro.kernels.core import default_interpret

    devs = jax.devices()
    check(devs[0].platform == "tpu",
          f"no TPU found: JAX's devices are {[d.platform for d in devs]}")
    check(not default_interpret(), "Pallas kernels would run in interpret mode")
    return jax, devs


def build(jax, seed):
    """compress → calibrate → quantize; returns the model pieces, the
    request pool and the fp32 reference logits of that pool."""
    import numpy as np

    from repro.configs import get_cnn_config
    from repro.models.cnn import SparseCNN

    cfg = dataclasses.replace(get_cnn_config(ARCH), kernel_mode="pallas")
    model = SparseCNN(cfg)
    ref_model = SparseCNN(dataclasses.replace(cfg, kernel_mode="ref"))
    k_init, k_cal, k_req = jax.random.split(jax.random.PRNGKey(seed), 3)
    shape = (cfg.image_size, cfg.image_size, cfg.in_channels)
    t0 = time.monotonic()
    params = model.compress(model.init(k_init))
    x_cal = jax.random.normal(k_cal, (8, *shape))
    _, stats = model.apply(params, x_cal, collect_act_stats=True)
    qparams = model.quantize(params, stats)
    n_compressed = sum(1 for p in qparams.values()
                       if type(p["w"]).__name__ == "QuantDBBWeight")
    log(f"{ARCH}: {len(model.layers())} layers, {n_compressed} compressed "
        f"(3/8 DBB, int8), calibrated in {time.monotonic() - t0:.1f}s")
    pool = np.asarray(jax.random.normal(k_req, (REQUESTS, *shape)),
                      np.float32)
    # the references run at full fp32 matmul precision (the TPU default
    # rounds fp32 operands to bf16, which the Pallas kernels do not)
    with jax.default_matmul_precision("highest"):
        fp32 = np.asarray(jax.jit(ref_model.apply)(params, pool))
    return model, ref_model, qparams, n_compressed, pool, fp32, cfg.num_classes


def ref_answers(jax, ref_model, qparams, buckets, pool):
    import numpy as np

    ref_set = ref_model.plan_set(qparams, buckets=buckets)
    with jax.default_matmul_precision("highest"):
        return np.asarray(ref_set.serve(pool))


def compile_buckets(plan_set, sample, put=None):
    """AOT-compile every bucket's program; returns {bucket: (seconds,
    [tpu_custom_call result shapes])}."""
    import numpy as np

    out = {}
    for b in plan_set.buckets:
        xb = np.zeros((b, *sample), np.float32)
        if put is not None:
            xb = put(xb)
        t0 = time.monotonic()
        compiled = plan_set.plans[b].lower(xb).compile()
        out[b] = (time.monotonic() - t0, custom_calls(compiled))
    return out


def check_answers(answers, want, fp32, *, classes, what):
    import numpy as np

    check(all(a is not None for a in answers),
          f"{sum(a is None for a in answers)} requests got no answer")
    for i, a in enumerate(answers):
        check(a.shape == (1, classes), f"answer {i} has shape {a.shape}")
        check(bool(np.isfinite(a).all()), f"answer {i} is not finite")
    rels = [rel_l2(a[0], want[i]) for i, a in enumerate(answers)]
    same = sum(bool((a[0] == want[i]).all()) for i, a in enumerate(answers))
    log(f"{len(answers)} answers vs {what}: {same} bit-identical, max "
        f"relative L2 {max(rels):.3e} (bound {REF_TOL})")
    check(max(rels) <= REF_TOL, f"an answer differs from {what} by "
          f"{max(rels):.3e} relative L2")
    if fp32 is not None:
        q = rel_l2(np.concatenate(answers), fp32)
        log(f"int8 answers vs fp32 model: relative L2 {q:.3e} (bound {INT8_TOL})")
        check(q <= INT8_TOL, f"int8 logits are {q:.3e} from fp32")


def offer(server, pool, burst, seed):
    """Offer each image of ``pool`` as a request to a started, warmed
    server: Poisson arrivals at 200/s, then ``burst`` all at once. Returns
    the answers (None where a request failed), failures by type, health."""
    import numpy as np

    from repro.launch.server import poisson_arrivals

    n_poisson = len(pool) - burst
    arrivals = np.concatenate([poisson_arrivals(200.0, n_poisson, seed=seed),
                               np.zeros(burst)])
    futures = []
    t0 = time.monotonic()
    for i, t_arr in enumerate(arrivals):
        if i == n_poisson:
            t0 = time.monotonic()  # the burst's clock starts at its arrival
        lag = t_arr - (time.monotonic() - t0)
        if lag > 0:
            time.sleep(lag)
        futures.append(server.submit(pool[i:i + 1]))
    answers, failures = [], {}
    timeout = server.request_timeout_s()
    for f in futures:
        try:
            answers.append(f.result(timeout=timeout))
        except Exception as e:  # noqa: BLE001 — tallied, then fails the run
            failures[type(e).__name__] = failures.get(type(e).__name__, 0) + 1
            answers.append(None)
    return answers, failures, server.health()


def check_served(server, failures, health, top):
    s = server.stats.summary()
    log(f"served {s['completed']}/{s['offered']} requests in {s['batches']} "
        f"batches, buckets {s['bucket_counts']}, p50 {s['p50_us']}us, "
        f"p99 {s['p99_us']}us, retraces after warmup "
        f"{server.retraces_after_warmup}, health {health['status']}")
    check(not failures, f"requests failed: {failures}")
    check(s["completed"] == REQUESTS and not (s["failed"] or s["expired"]
                                              or s["rejected"]),
          f"not every request completed: {s}")
    check(not health["demoted"], f"demoted buckets: {health['demoted']}")
    check(health["status"] == "ready", f"health is {health['status']}")
    check(str(top) in s["bucket_counts"], f"no batch filled bucket {top}")


def serve_one_chip(jax, seed):
    from repro.launch.server import CNNServer
    from repro.launch.supervisor import Supervisor

    model, ref_model, qparams, n_compressed, pool, fp32, classes = build(jax, seed)
    plan_set = model.plan_set(qparams, max_batch=8)
    top = plan_set.buckets[-1]
    check(top >= 8, f"largest bucket {top} < 8 never runs the head kernel")
    compiled = compile_buckets(plan_set, pool.shape[1:])
    for b, (sec, calls) in compiled.items():
        log(f"bucket {b}: compiled in {sec:.1f}s, {len(calls)} tpu_custom_call")
        check(calls, f"bucket {b} runs no Pallas kernel")
    calls = compiled[top][1]
    check(len(calls) >= n_compressed + 1, f"largest bucket holds {len(calls)} "
          f"Pallas kernels, fewer than {n_compressed} compressed layers + stem")
    with Supervisor(CNNServer(plan_set, max_wait_ms=5.0)) as sup:
        sup.warmup()
        answers, failures, health = offer(sup, pool, 2 * top, seed)
    check_served(sup, failures, health, top)
    want = ref_answers(jax, ref_model, qparams, plan_set.buckets, pool)
    check_answers(answers, want, fp32, classes=classes, what="the ref plan")


def serve_four_chips(jax, devs, seed):
    import numpy as np

    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.launch.mesh import auto_mesh
    from repro.launch.server import CNNServer

    dp = 4
    check(len(devs) >= dp, f"--chips 4 needs four devices, JAX has {len(devs)}")
    model, _, qparams, _, pool, _, classes = build(jax, seed)
    mesh = auto_mesh((dp, 1), ("data", "model"), devices=devs[:dp])
    sharding = NamedSharding(mesh, P("data"))
    plan_set = model.plan_set(qparams, max_batch=8 * dp, dp=dp)
    top = plan_set.buckets[-1]
    single = np.asarray(plan_set.serve(pool))  # one device, same ladder
    srv = CNNServer(plan_set, max_wait_ms=5.0, mesh=mesh)

    def put(x):
        return jax.device_put(x, sharding)

    for b, (sec, calls) in compile_buckets(srv.plan_set, pool.shape[1:],
                                           put).items():
        rows = sorted({int(re.search(r"\[(\d+)", c).group(1)) for c in calls
                       if re.search(r"\[\d+,\d+,", c)})
        log(f"bucket {b} on {dp} chips: compiled in {sec:.1f}s, {len(calls)} "
            f"tpu_custom_call, conv kernel batch rows {rows}")
        check(rows == [b // dp], f"bucket {b}: conv kernels see batch {rows}, "
              f"not the per-device {b // dp}")
    y = srv.plan_set.plans[top].serve(put(pool[:top]))
    spread = sorted((s.device.id, s.data.shape[0]) for s in y.addressable_shards)
    log(f"largest bucket's output shards (device, rows): {spread}")
    check(len({d for d, _ in spread}) == dp, "the batch did not spread over "
          f"{dp} devices: {spread}")
    with srv:
        srv.warmup()
        answers, failures, health = offer(srv, pool, top, seed)
    check_served(srv, failures, health, top)
    check_answers(answers, single, None, classes=classes,
                  what="the same requests on one device")


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="1: the serving path; 4: mesh data-parallel serving "
                         "against one device, and nothing else")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    try:
        jax, devs = setup()
        log(f"device {devs[0].device_kind} x{len(devs)}")
        if args.chips == 4:
            serve_four_chips(jax, devs, args.seed)
        else:
            serve_one_chip(jax, args.seed)
    except SmokeFailure as e:
        print(f"[chip_smoke] FAILED: {e}", file=sys.stderr, flush=True)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": devs[0].platform, "kind": devs[0].device_kind,
        "count": len(devs)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
