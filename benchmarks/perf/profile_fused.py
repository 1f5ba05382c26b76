"""Attribute fused-path wall time to its components (DESIGN.md §12).

``python -m benchmarks.perf.profile_fused [reps]`` — the diagnosis
harness behind the measured-wall-time gates. Four sections, written
machine-readable to ``PROF_fused.json``:

1. **epilogue ablation ladder** — one quantized conv layer measured at
   every epilogue depth (bare kernel → +bias → +bias+relu → full fused
   requant) plus the unfused kernel + standalone-XLA-epilogue program,
   each timed *interleaved against the bare kernel* (min-of-k, so the
   deltas are drift-free). If the fused flush serialized the epilogue,
   it would show here as a ladder step far above the XLA cost of the
   same op; profiling on CPU shows the steps are noise-level — the
   PR-6-era "fused slower than unfused" artifact was measurement
   methodology, not kernel structure.
2. **compiled-HLO breakdown** — per-opcode instruction counts, fusion
   and custom-call (≈ kernel launch) totals, and cost-analysis
   bytes/flops for the fused vs unfused layer programs: *where* the
   wall-time delta comes from without a hardware profiler.
3. **per-stage chain attribution** — the int8-resident smoke-CNN serving
   chain (pallas mode), each frozen stage closure timed in isolation on
   its actual intermediate input, vs the end-to-end chain: which layer
   dominates, and how much dispatch overhead the single-jit chain saves
   over the sum of stages.
4. **pad_tile audit** — for every conv/matmul shape the chain launches,
   whether the ops-level pad-and-slice escape hatch would actually pad
   (on the evenly-divisible smoke shapes it must not; ragged-shape
   correctness is covered by tests/test_fused_epilogue.py).
"""
import dataclasses
import json
import pathlib
import sys

import jax
import jax.numpy as jnp

from benchmarks.timing import interleaved_samples_us, noise_frac
from repro.core import quant
from repro.core.vdbb import DBBFormat, dbb_encode_conv
from repro.kernels import ops
from repro.kernels.core import pad_tile, pick_tile
from repro.xla_utils import hlo_op_breakdown

OUT_PATH = pathlib.Path(__file__).resolve().parents[2] / "PROF_fused.json"

WARMUP = 2
REPS = 15


def _vs_base(base_fn, fn, reps):
    """(base_us, fn_us, noise) — min-of-k, interleaved against the base."""
    sb, sf = interleaved_samples_us(base_fn, fn, warmup=WARMUP, reps=reps)
    return min(sb), min(sf), max(noise_frac(sb), noise_frac(sf))


def ablation_ladder(reps):
    """Section 1+2: one quantized conv layer at every epilogue depth."""
    n, h, w, c, f = 2, 16, 16, 32, 64
    k1, k2, k3 = jax.random.split(jax.random.PRNGKey(0), 3)
    x = jax.random.normal(k1, (n, h, w, c))
    w4 = jax.random.normal(k2, (3, 3, c, f))
    b = jax.random.normal(k3, (f,))
    fmt = DBBFormat(8, 3, "matrix")
    qw = quant.quantize_dbb(dbb_encode_conv(w4, fmt, prune=True))
    s_a = quant.dynamic_act_scale(x)
    out_s = jnp.float32(0.05)
    xq = quant.quantize(x, s_a)

    def conv(**kw):
        return lambda xq: ops.quant_conv(xq, qw, 3, 3, s_a, bf=f,
                                         interpret=True, **kw)

    variants = {
        "kernel_only": conv(),
        "fused_bias": conv(bias=b),
        "fused_bias_relu": conv(bias=b, relu=True),
        "fused_full": conv(bias=b, relu=True, out_scale=out_s),
    }
    kernel = conv()

    def unfused_full(xq):
        return quant.quantize(jax.nn.relu(kernel(xq) + b), out_s)

    variants["unfused_full"] = unfused_full

    base = jax.jit(variants["kernel_only"])
    jax.block_until_ready(base(xq))
    ladder = {}
    for name, fn in variants.items():
        jf = jax.jit(fn)
        t_base, t_fn, nz = _vs_base(lambda: base(xq), lambda: jf(xq), reps)
        ladder[name] = {
            "us": t_fn,
            "delta_vs_kernel_us": t_fn - t_base,
            "noise_frac": round(nz, 4),
        }
    hlo = {
        label: hlo_op_breakdown(fn, xq)
        for label, fn in (("fused_full", variants["fused_full"]),
                          ("unfused_full", unfused_full))
    }
    return ladder, hlo


def chain_attribution(reps):
    """Section 3: per-stage wall time of the int8-resident serving chain."""
    from repro.configs import smoke_cnn_config
    from repro.models.cnn import SparseCNN

    cfg = dataclasses.replace(
        smoke_cnn_config("sparse-cnn-tiny", sparsity=0.625),
        kernel_mode="pallas")
    model = SparseCNN(cfg)
    params = model.compress(model.constrain(model.init(jax.random.PRNGKey(0))))
    xb = jax.random.normal(
        jax.random.PRNGKey(1), (4, cfg.image_size, cfg.image_size, cfg.in_channels))
    _, stats = model.apply(params, xb, collect_act_stats=True)
    qparams = model.quantize(params, stats)
    plan = model.plan(qparams, batch=4)

    e2e = jax.jit(plan.serve)
    jax.block_until_ready(e2e(xb))
    stages = []
    x = xb
    for lp in plan.layers:
        run = jax.jit(lp.run)
        y = jax.block_until_ready(run(x))
        se, sr = interleaved_samples_us(lambda: e2e(xb), lambda: run(x),
                                        warmup=1, reps=reps)
        stages.append({
            "name": lp.name, "kind": lp.kind, "tiles": dict(lp.tiles),
            "us": min(sr), "in_dtype": str(x.dtype), "out_dtype": str(y.dtype),
            "noise_frac": round(max(noise_frac(se), noise_frac(sr)), 4),
        })
        x = y
    t_e2e = min(interleaved_samples_us(lambda: e2e(xb), lambda: e2e(xb),
                                       warmup=1, reps=reps)[0])
    return {
        "stages": stages,
        "e2e_us": t_e2e,
        "sum_of_stages_us": sum(s["us"] for s in stages),
    }


def pad_audit():
    """Section 4: would pad_tile actually pad on the chain's shapes?"""
    # (dim, requested-or-None, default) for the launch dims the smoke chain
    # resolves through the ops-level pad-and-slice entry points
    cases = [
        ("conv_bf_32", 32, None, 128),
        ("conv_bf_64", 64, None, 128),
        ("matmul_bm_4", 4, None, 128),   # head GEMM rows = batch
        ("matmul_bn_10", 10, None, 128),  # head GEMM cols = classes
    ]
    out = []
    for name, dim, tile, default in cases:
        t, padded = pad_tile(dim, tile, default)
        out.append({
            "case": name, "dim": dim, "tile": t, "padded_dim": padded,
            "pads": padded != dim, "pick_tile": pick_tile(dim, default),
        })
    return out


def main(reps: int = REPS) -> None:
    ladder, hlo = ablation_ladder(reps)
    chain = chain_attribution(reps)
    pads = pad_audit()
    results = {
        "harness": {"stat": "min", "reps": reps, "warmup": WARMUP,
                    "interleaved": True, "backend": jax.default_backend()},
        "ablation_us": ladder,
        "hlo": hlo,
        "chain": chain,
        "pad_audit": pads,
    }
    OUT_PATH.write_text(json.dumps(results, indent=2))

    print(f"== epilogue ablation (min of {reps}, interleaved vs bare kernel) ==")
    for name, r in ladder.items():
        print(f"  {name:18s} {r['us']:9.1f}us  (+{r['delta_vs_kernel_us']:7.1f}us"
              f" vs kernel, noise {r['noise_frac']:.0%})")
    print("== compiled HLO ==")
    for label, h in hlo.items():
        print(f"  {label:14s} instrs={h['n_instructions']:4d} "
              f"fusions={h['n_fusions']:3d} custom_calls={h['n_custom_calls']}"
              f" bytes={h['bytes_accessed']} flops={h['flops']}")
    print("== int8-resident chain, per stage ==")
    for s in chain["stages"]:
        print(f"  {s['name']:8s} {s['kind']:6s} {s['us']:9.1f}us  "
              f"{s['in_dtype']}->{s['out_dtype']}  tiles={s['tiles']}")
    print(f"  e2e {chain['e2e_us']:.1f}us vs sum-of-stages "
          f"{chain['sum_of_stages_us']:.1f}us")
    print("== pad_tile audit ==")
    for p in pads:
        mark = "PADS" if p["pads"] else "exact"
        print(f"  {p['case']:14s} dim={p['dim']:4d} tile={p['tile']:4d} "
              f"padded={p['padded_dim']:4d}  {mark}")
    print(f"wrote {OUT_PATH.name}")


if __name__ == "__main__":
    main(int(sys.argv[1]) if len(sys.argv) > 1 else REPS)
