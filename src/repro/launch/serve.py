"""Batched serving driver: prefill + decode loop with VDBB-compressed
weights — the paper's bandwidth win applied where TPU decode is most
weight-bandwidth-bound.

  PYTHONPATH=src python -m repro.launch.serve --arch codeqwen1.5-7b --smoke \
      --batch 4 --prompt-len 32 --gen 16

CNN archs serve through a **frozen plan** (DESIGN.md §10): INT8
quantization is calibrated, every layer's tiles (``kernels/core.py``'s
rule) + staged weight buffers are resolved once by ``SparseCNN.plan()``,
and the timed loop runs the single-dispatch ``plan.serve`` hot path.
``--no-plan`` serves the unplanned path — jitted once, so the comparison
measures the plan's staging win, not python dispatch overhead.

  PYTHONPATH=src python -m repro.launch.serve --arch sparse-cnn-tiny --smoke \
      --batch 4 --steps 16

``--server`` runs the **continuous-batching tier** (DESIGN.md §11)
instead of a fixed-batch loop: a bucketed plan set (1/2/…/--max-batch),
the request queue + micro-batcher of ``repro.launch.server``, and a
Poisson load generator at ``--rate`` requests/s (default: auto-picked
at ~50% of measured capacity). The server runs under the §15
``Supervisor`` (crash → supervised restart with requeue), and
``--reload-every N`` hot-reloads the weights from a checksummed
checkpoint every N requests — an atomic plan swap mid-traffic. Reports
p50/p99 latency, sustained throughput, aggregation shape, supervisor
state (restarts / requeued / reloads / demoted buckets / health), and
the zero-retrace check. It exits 1 when a request failed or expired, a
bucket was demoted, or health is not ``ready`` (shed requests are
reported, not failures):

  PYTHONPATH=src python -m repro.launch.serve --arch sparse-cnn-tiny --smoke \
      --server --max-batch 8 --max-wait-ms 5 --requests 64 --reload-every 24

``--lm-plan`` serves LM prefill through the same frozen-plan machinery
(DESIGN.md §13): compress → calibrate → INT8-quantize → ``LM.plan()``,
with a bit-identity check against the jitted unplanned forward:

  PYTHONPATH=src python -m repro.launch.serve --arch qwen2-tiny --lm-plan \
      --batch 2 --prompt-len 32
"""
from __future__ import annotations

import argparse
import dataclasses
import time

import jax
import jax.numpy as jnp

from repro.configs import CNN_ARCHS, cnn_model, get_cnn_config, get_config, \
    make_batch, smoke_cnn_config, smoke_config
from repro.models.model import LM
from repro.train.step import make_prefill, make_serve_step


def generate(model: LM, params, prompt_batch, *, gen_len: int, max_len: int):
    """Greedy batched generation. Returns (tokens, steps/s)."""
    cfg = model.cfg
    prefill = jax.jit(make_prefill(model))
    step_fn = jax.jit(make_serve_step(model))
    b = prompt_batch["tokens"].shape[0]
    plen = prompt_batch["tokens"].shape[1]
    logits, caches = prefill(params, prompt_batch)

    # pad the prefill cache out to max_len capacity
    def pad_to_cap(a):
        if a.ndim >= 3 and a.shape[-3] == plen:
            pad = [(0, 0)] * a.ndim
            pad[-3] = (0, max_len - plen)
            return jnp.pad(a, pad)
        if a.ndim >= 2 and a.shape[-2] == plen and a.shape[-1] != plen:
            pad = [(0, 0)] * a.ndim
            pad[-2] = (0, max_len - plen)
            return jnp.pad(a, pad)
        return a

    cache = jax.tree_util.tree_map(pad_to_cap, caches)
    tok = jnp.argmax(logits[:, -1:], axis=-1).astype(jnp.int32)
    if cfg.frontend == "audio":
        tok = jnp.broadcast_to(tok[..., None] % cfg.codebook_vocab, (b, 1, cfg.num_codebooks))
    out = [tok]
    t0 = time.time()
    for i in range(gen_len - 1):
        step = {"tokens": tok}
        if cfg.cross_attn and "memory" in prompt_batch:
            step["memory"] = prompt_batch["memory"]
        logits, cache = step_fn(params, cache, step, jnp.int32(plen + i))
        tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        if cfg.frontend == "audio":
            tok = jnp.broadcast_to(tok[..., None] % cfg.codebook_vocab, (b, 1, cfg.num_codebooks))
        out.append(tok)
    dt = time.time() - t0
    toks = jnp.concatenate(out, axis=1)
    return toks, (gen_len - 1) / max(dt, 1e-9)


def serve_cnn(args):
    """INT8 CNN serving through a frozen plan (DESIGN.md §10)."""
    cfgf = smoke_cnn_config if args.smoke else get_cnn_config
    sparsity = None if args.dense else args.sparsity
    cfg = dataclasses.replace(
        cfgf(args.arch, sparsity=sparsity), kernel_mode="pallas"
    )
    model = cnn_model(cfg)
    params = model.compress(model.init(jax.random.PRNGKey(0)))
    xb = jax.random.normal(
        jax.random.PRNGKey(1),
        (args.batch, cfg.image_size, cfg.image_size, cfg.in_channels),
    )
    _, stats = model.apply(params, xb, collect_act_stats=True)
    qparams = model.quantize(params, stats)
    print(f"[serve] {cfg.name}: INT8-calibrated, nnz={cfg.fmt.nnz}/{cfg.fmt.bz}")
    if args.server:
        return serve_cnn_continuous(args, model, qparams, xb)
    if args.plan:
        plan = model.plan(qparams, batch=args.batch)
        print(f"[serve] frozen plan: {len(plan.layers)} stages, "
              f"tiles frozen for {len(plan.tiles)} layers")
        step = plan.serve
    else:
        # jitted once: the comparison vs --plan then measures what plans
        # save (staging, weight folding, tile pinning), not retrace/
        # python-dispatch overhead the unplanned path would otherwise pay
        # on every timed call.
        print("[serve] unplanned path, jitted once (--no-plan)")
        step = jax.jit(lambda xb: model.apply(qparams, xb))
    from repro.xla_utils import median_time_us  # the shared bench harness

    logits = step(xb)
    us = median_time_us(step, xb, warmup=1, reps=args.steps)
    print(f"served batches of {args.batch} ({logits.shape} logits) at "
          f"{1e6 / max(us, 1e-9):.2f} steps/s (median of {args.steps})")
    return logits


def serve_cnn_continuous(args, model, qparams, xpool):
    """The §11 serving tier under a Poisson load (``--server``), with the
    §14 robustness knobs: bounded admission (``--max-queue`` /
    ``--shed``), per-request deadlines (``--deadline-ms``), and a
    client-side timeout derived from the server's own deadline/max-wait
    config + measured bucket time (no hardcoded constant). Per-request
    failures (shed, expired, faulted) are tallied into the summary
    instead of crashing the run on the first bad future.

    The server runs under the §15 :class:`Supervisor`: a dispatcher
    crash restarts it (requeuing undispatched requests) instead of
    failing the run, and ``--reload-every N`` exercises the hot-reload
    path live — the quantized weights are checkpointed (checksummed) at
    startup and every N requests the supervisor restores, verifies,
    rebuilds, and atomically swaps the plan set mid-traffic."""
    from repro.launch.server import CNNServer, Overloaded, ServerCrashed, \
        auto_rate, poisson_arrivals
    from repro.launch.supervisor import Supervisor

    sample_shape = xpool.shape[1:]
    plan_set = model.plan_set(qparams, max_batch=args.max_batch)
    print(f"[serve] plan set: buckets {plan_set.buckets}, "
          f"max-wait {args.max_wait_ms}ms, max-queue {args.max_queue} "
          f"({args.shed})")
    rate = args.rate
    if rate is None:
        rate, bucket_us = auto_rate(plan_set, sample_shape)
        print(f"[serve] auto rate: {rate:.1f} rps "
              f"(~50% of capacity; largest bucket {bucket_us:.0f}us)")
    arrivals = poisson_arrivals(rate, args.requests, seed=0)
    # clients hand the server host data: a jax slice per submit would
    # enqueue onto the same device stream the serving batches run on
    import numpy as np

    pool = np.asarray(xpool)
    deadline_s = args.deadline_ms / 1e3 if args.deadline_ms else None
    srv = CNNServer(plan_set, max_wait_ms=args.max_wait_ms,
                    max_queue=args.max_queue, shed=args.shed)
    sup = Supervisor(
        srv,
        rebuild=lambda tree: model.plan_set(tree, max_batch=args.max_batch),
        template=qparams,
    )
    ckpt_dir = None
    if args.reload_every:
        import tempfile

        from repro.checkpoint.store import save as ckpt_save

        ckpt_dir = tempfile.mkdtemp(prefix="serve-ckpt-")
        ckpt_save(ckpt_dir, 1, qparams)
        print(f"[serve] hot-reload every {args.reload_every} requests from "
              f"checksummed checkpoint at {ckpt_dir}")
    results, failures = [], {}
    with sup:
        sup.warmup(sample_shape)
        futures = []
        t0 = time.monotonic()
        for i, t_arr in enumerate(arrivals):
            lag = t_arr - (time.monotonic() - t0)
            if lag > 0:
                time.sleep(lag)
            if ckpt_dir is not None and i and i % args.reload_every == 0:
                step, fp = sup.reload(ckpt_dir)
                print(f"[serve] hot reload @req {i}: step {step}, plan "
                      f"{fp[:12]} swapped mid-traffic")
            try:
                futures.append(
                    sup.submit(pool[i % pool.shape[0]][None],
                               deadline_s=deadline_s))
            except Overloaded as e:  # shed — the run keeps going
                failures["Overloaded"] = failures.get("Overloaded", 0) + 1
                futures.append(None)
                if failures["Overloaded"] == 1:
                    print(f"[serve] shedding (retry-after "
                          f"{e.retry_after_s * 1e3:.1f}ms)")
            except ServerCrashed:  # restart gap — tally, keep offering
                failures["ServerCrashed"] = failures.get("ServerCrashed", 0) + 1
                futures.append(None)
        # derived from max_wait + backlog x measured bucket time —
        # replaces the old hardcoded f.result(timeout=120)
        timeout_s = sup.request_timeout_s()
        for f in futures:
            if f is None:
                results.append(None)
                continue
            try:
                results.append(f.result(timeout=timeout_s))
            except Exception as e:  # noqa: BLE001 — tally, don't crash the run
                failures[type(e).__name__] = failures.get(type(e).__name__, 0) + 1
                results.append(None)
        health = sup.health()
    sup.stats.assert_accounting()
    s = sup.stats.summary()
    print(f"[serve] {s['completed']}/{s['offered']} requests in {s['batches']} "
          f"batches {s['bucket_counts']} (padded_frac {s['padded_frac']})")
    if failures:
        tally = ", ".join(f"{k} x{v}" for k, v in sorted(failures.items()))
        print(f"[serve] per-request failures: {tally} "
              f"(shed_rate {s['shed_rate']}, expired {s['expired']}, "
              f"failed {s['failed']})")
    demoted = health.get("demoted", {})
    print(f"[serve] supervisor: restarts {s['restarts']}  "
          f"requeued {s['requeued']}  reloads {s['reloads']}  "
          f"demoted buckets {sorted(demoted) if demoted else 'none'}")
    print(f"[serve] p50 {s['p50_us']:.0f}us  p99 {s['p99_us']:.0f}us  "
          f"goodput {s['throughput_rps']:.1f} rps  "
          f"client timeout {timeout_s:.1f}s (derived)  "
          f"retraces after warmup: {sup.retraces_after_warmup}  "
          f"health: {health['status']}")
    waited = s["queue_wait_s"] / max(s["dispatched_requests"], 1)
    print(f"[serve] host: queue wait {waited * 1e3:.2f}ms per request "
          f"({s['dispatched_requests']} dispatched)  overlapped "
          f"{s['overlapped_batches']}/{s['batches']} batches  warm-up "
          f"{s['warmup_s']:.2f}s  gc {s['gc_collections']} passes "
          f"{s['gc_s'] * 1e3:.1f}ms")
    problems = [f"{v} request(s) {k}" for k, v in sorted(failures.items())
                if k != "Overloaded"]  # a shed is admission, not a failure
    if demoted:
        problems.append(f"buckets demoted {sorted(demoted)}")
    if health["status"] != "ready":
        problems.append(f"health {health['status']}")
    if problems:
        print(f"[serve] FAILED: {'; '.join(problems)}")
        raise SystemExit(1)
    return results


def serve_lm_plan(args):
    """LM prefill served through a frozen ModelPlan (DESIGN.md §13):
    compress → calibrate → INT8-quantize → plan, then a bit-identity
    check against the jitted unplanned forward and a timed comparison."""
    sparsity = None if args.dense else args.sparsity
    cfg = (smoke_config if args.smoke else get_config)(args.arch, sparsity=sparsity)
    if cfg.dbb is None:
        raise SystemExit("--lm-plan needs a DBB config (drop --dense)")
    model = LM(cfg)
    params = model.compress(model.init(jax.random.PRNGKey(0)))
    batch = make_batch(cfg, batch=args.batch, seq=args.prompt_len, kind="serve")
    tokens = batch["tokens"]
    _, stats = model.forward(params, batch, collect_act_stats=True)
    qparams = model.quantize(params, stats)
    print(f"[serve] {cfg.name}: INT8-calibrated VDBB LM "
          f"(nnz={cfg.dbb.nnz}/{cfg.dbb.bz}, kernel_mode={cfg.kernel_mode})")
    plan = model.plan(qparams, batch=args.batch, seq=args.prompt_len)
    print(f"[serve] frozen plan: {len(plan.layers)} stages")
    # the §14 admission check guards the LM path too: token batches are
    # validated against the plan's sample spec before any dispatch
    from repro.launch.server import validate_request

    for row in tokens:
        validate_request(row[None], plan.sample_spec)
    ref = jax.jit(lambda t: model.forward(qparams, {"tokens": t}))
    bit = bool((plan(tokens) == ref(tokens)).all())
    print(f"[serve] plan vs unplanned forward bit-identical: {bit}")
    from repro.xla_utils import median_time_us

    plan_us = median_time_us(plan.serve, tokens, warmup=1, reps=args.steps)
    ref_us = median_time_us(ref, tokens, warmup=1, reps=args.steps)
    print(f"[serve] prefill ({args.batch}x{args.prompt_len}): plan "
          f"{plan_us:.0f}us vs unplanned {ref_us:.0f}us")
    return bit


def main(argv=None):
    from repro.xla_utils import use_compile_cache

    use_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--sparsity", type=float, default=0.625)
    ap.add_argument("--dense", action="store_true")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--gen", type=int, default=16)
    ap.add_argument("--steps", type=int, default=16,
                    help="timed forward passes (CNN serving)")
    ap.add_argument("--plan", action=argparse.BooleanOptionalAction, default=True,
                    help="CNN: serve through a frozen plan (--no-plan = per-call path)")
    ap.add_argument("--lm-plan", action="store_true",
                    help="LM: serve prefill through a frozen ModelPlan "
                         "(DESIGN §13) instead of the decode loop")
    ap.add_argument("--server", action="store_true",
                    help="CNN: continuous-batching tier (DESIGN §11) under a "
                         "Poisson load instead of a fixed-batch loop")
    ap.add_argument("--max-batch", type=int, default=8,
                    help="server: aggregation cap = largest plan bucket")
    ap.add_argument("--max-wait-ms", type=float, default=5.0,
                    help="server: max queueing delay before a partial batch "
                         "dispatches")
    ap.add_argument("--requests", type=int, default=64,
                    help="server: load-generator request count")
    ap.add_argument("--rate", type=float, default=None,
                    help="server: offered load in requests/s "
                         "(default: ~50%% of measured capacity)")
    ap.add_argument("--max-queue", type=int, default=None,
                    help="server: admission bound — pending requests beyond "
                         "this are shed per --shed (default: unbounded)")
    ap.add_argument("--shed", choices=("reject", "block"), default="reject",
                    help="server: overload policy at --max-queue — reject "
                         "(typed Overloaded with retry-after) or block "
                         "(backpressure the submitter)")
    ap.add_argument("--deadline-ms", type=float, default=None,
                    help="server: per-request deadline; requests that "
                         "cannot be served in time fail with "
                         "DeadlineExceeded instead of wasting a dispatch")
    ap.add_argument("--reload-every", type=int, default=None,
                    help="server: checkpoint the quantized weights at "
                         "startup and hot-reload them (verify → rebuild → "
                         "atomic plan swap, DESIGN §15) every N requests "
                         "mid-traffic")
    args = ap.parse_args(argv)

    if args.arch in CNN_ARCHS:
        return serve_cnn(args)
    if args.lm_plan:
        return serve_lm_plan(args)

    sparsity = None if args.dense else args.sparsity
    cfg = (smoke_config if args.smoke else get_config)(args.arch, sparsity=sparsity)
    model = LM(cfg)
    params = model.init(jax.random.PRNGKey(0))
    if cfg.dbb is not None and cfg.serve_compressed:
        params = model.compress(params)
        print("[serve] weights compressed to VDBB layout "
              f"(nnz={cfg.dbb.nnz}/{cfg.dbb.bz})")
    prompt = make_batch(cfg, batch=args.batch, seq=args.prompt_len, kind="serve")
    toks, rate = generate(
        model, params, prompt, gen_len=args.gen, max_len=args.prompt_len + args.gen
    )
    print(f"generated {toks.shape} tokens at {rate:.2f} steps/s")
    return toks


if __name__ == "__main__":
    main()
