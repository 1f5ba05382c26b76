"""LM VDBB datapath wall time + plan parity (DESIGN.md §13/§12).

Three measurements, written machine-readable to ``BENCH_lm.json``:

1. **compressed vs dense GEMM wall time** — the transformer projection
   shapes (attention proj and MLP up, qwen2-like K:N ratios) through
   ``dbb_matmul_gather_ref`` vs the dense ``x @ W`` it replaced. This is
   the gate: before PR 8 ``apply_linear`` silently densified compressed
   LM weights, so the compressed path MUST now be no slower than dense
   (it computes nnz/bz of the MACs).
2. **int8 vs fp32 GEMM wall time** — the same shapes through
   ``quant_matmul_gather_ref``. Report-only: XLA:CPU has no native int8
   MXU path so int8 loses on this backend; the number is recorded for
   the trajectory, not gated. The gather-form vs decode-form quantized
   GEMM is asserted bit-identical (integer sums are order-independent).
3. **plan vs unplanned LM prefill** — the registered ``qwen2-tiny``
   config, compressed + INT8-calibrated, served through a frozen
   ``LM.plan()`` vs the jitted unplanned forward, asserted bit-identical
   (gated in check_regression).

Measurement policy (§12): paired claims sampled interleaved, reduced
with ``min`` over generous reps; ``noise_frac`` persisted so
``check_regression.py`` widens margins on noisy hosts.
"""
import json
import pathlib

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.timing import interleaved_samples_us, noise_frac
from repro.core import quant
from repro.core.vdbb import DBBFormat, dbb_decode, dbb_encode, \
    dbb_matmul_gather_ref

OUT_PATH = pathlib.Path(__file__).resolve().parents[1] / "BENCH_lm.json"

WARMUP = 2
REPS = 25
STAT = "min"

# (label, m, k, n): qwen2-like projection shapes at CPU-benchable size —
# attention out-proj (square) and MLP up-proj (K:N = 1:2)
GEMM_SHAPES = (
    ("attn_proj", 256, 512, 512),
    ("mlp_up", 256, 512, 1024),
)


def _paired(fn_a, fn_b):
    """min-of-k interleaved wall times + the batch noise estimate."""
    sa, sb = interleaved_samples_us(fn_a, fn_b, warmup=WARMUP, reps=REPS)
    return min(sa), min(sb), max(noise_frac(sa), noise_frac(sb))


def run(report):
    results = {
        "gemms": [], "plan": {}, "noise_frac": {},
        "harness": {"stat": STAT, "reps": REPS, "warmup": WARMUP,
                    "interleaved": True, "backend": jax.default_backend()},
    }
    fmt = DBBFormat(8, 3, "matrix")

    # --- 1/2. projection GEMMs: dense vs compressed vs int8 --------------
    for label, m, k, n in GEMM_SHAPES:
        kx, kw = jax.random.split(jax.random.PRNGKey(0))
        x = jax.random.normal(kx, (m, k), jnp.float32)
        dw = dbb_encode(jax.random.normal(kw, (k, n), jnp.float32),
                        fmt, prune=True)
        wd = dbb_decode(dw)  # dense-with-zeros: what the old path matmul'd
        qw = quant.quantize_dbb(dw)
        s_a = quant.dynamic_act_scale(x)
        xq = quant.quantize(x, s_a)

        # gather-form == decode-form quantized GEMM, bitwise (int32 sums)
        np.testing.assert_array_equal(
            np.asarray(quant.quant_matmul_gather_ref(xq, qw, s_a)),
            np.asarray(quant.quant_matmul_ref(xq, qw, s_a)),
        )

        dense = jax.jit(lambda x, wd=wd: x @ wd)
        comp = jax.jit(lambda x, dw=dw: dbb_matmul_gather_ref(x, dw))
        qgemm = jax.jit(
            lambda xq, qw=qw, s=s_a: quant.quant_matmul_gather_ref(xq, qw, s))
        t_d, t_c, nz = _paired(lambda: dense(x), lambda: comp(x))
        t_q, _, nz_q = _paired(lambda: qgemm(xq), lambda: dense(x))
        results["gemms"].append(dict(
            name=label, m=m, k=k, n=n, nnz=fmt.nnz, bz=fmt.bz,
            dense_us=t_d, compressed_us=t_c, int8_us=t_q,
        ))
        results["noise_frac"][label] = round(max(nz, nz_q), 4)
        report(f"lm/{label}", t_c,
               f"dense {t_d:.0f}us int8 {t_q:.0f}us (noise {nz:.0%}; "
               f"{m}x{k}x{n}, nnz {fmt.nnz}/{fmt.bz})")

    # --- 3. qwen2-tiny prefill: frozen plan vs unplanned forward ---------
    from repro.configs import get_config
    from repro.models.model import LM

    cfg = get_config("qwen2-tiny")
    model = LM(cfg)
    batch, seq = 2, 32
    params = model.compress(model.constrain(model.init(jax.random.PRNGKey(0))))
    tokens = jax.random.randint(
        jax.random.PRNGKey(1), (batch, seq), 0, cfg.vocab_size)
    _, stats = model.forward(
        params, {"tokens": tokens}, collect_act_stats=True)
    qparams = model.quantize(params, stats)

    unplanned = jax.jit(lambda t: model.forward(qparams, {"tokens": t}))
    plan = model.plan(qparams, batch=batch, seq=seq)
    bit = bool((plan(tokens) == unplanned(tokens)).all())
    assert bit, "frozen plan diverged from the unplanned forward"
    t_p, t_u, nz = _paired(lambda: plan.serve(tokens), lambda: unplanned(tokens))
    results["plan"] = {
        "model": cfg.name, "batch": batch, "seq": seq,
        "stages": len(plan.layers), "bit_identical": bit,
        "plan_us": t_p, "unplanned_us": t_u,
    }
    results["noise_frac"]["plan"] = round(nz, 4)
    report("lm/plan_prefill", t_p,
           f"unplanned {t_u:.0f}us (noise {nz:.0%}), bit-identical, "
           f"{len(plan.layers)} stages, {cfg.name} {batch}x{seq}")

    OUT_PATH.write_text(json.dumps(results, indent=2))
    report("lm/json", 0.0, f"wrote {OUT_PATH.name}")


if __name__ == "__main__":
    run(lambda name, us, derived="": print(f"{name},{us:.1f},{derived}"))
