"""Benchmark-artifact regression gate (CI).

Compares the freshly-written ``BENCH_fused.json`` against the *committed*
baseline floors in ``benchmarks/bench_baselines.json`` (the generated
artifacts themselves are gitignored): per-layer fused-epilogue savings
fractions must not regress below the baseline (small tolerance for
rounding) and must in any case stay above the §9 acceptance floor of 25%.

``BENCH_fused.json`` is additionally gated on **measured wall time**
(DESIGN.md §12 — wall time is the perf contract, not the modeled bytes):
the fused conv layer must not lose to the kernel + standalone-XLA-epilogue
program, and the int8-resident CNN chain must not lose to the
per-layer-dequant path. Both pairs are measured interleaved min-of-k by
``bench_fused.py``; the gate margin is ``fused_wall_margin`` widened by
the measured host noise of the same sample batch
(``× (1 + min(noise_frac, fused_noise_cap))``) — host-speed-relative, so
a contended CI box widens its own tolerance instead of flaking, while a
genuine fusion regression still trips it.

Every artifact is first checked against a minimal schema (required keys
present, numbers finite and positive) so a truncated or hand-edited file
fails loudly instead of silently passing vacuous gates.

``BENCH_serve.json`` gates the §11 serving tier on **measured wall
time** (the first slice of the ROADMAP "wall time is the contract"
item): the frozen bucket plan must not lose to the jitted-once
unplanned path beyond ``serve_plan_margin``, every load pattern must
complete all offered requests with **zero retraces after warmup**, and
p99 latency must stay under its self-calibrated bound
(``serve_p99_margin × (max_wait + (queue depth + 2) × measured bucket
time)`` — host-speed-relative, so the gate catches order-of-magnitude
tail-latency regressions without hardcoding microseconds). Bucketed
serving must also have been bit-identical to per-request serving. The
§14 robustness scenarios in the same artifact are gated by
``check_chaos``: blast-radius isolation (innocent survival exactly 1.0,
typed poison failures, zero bisect retraces), overload shedding
(``shed_rate > 0`` at 2x capacity, admitted p99 within the bounded-queue
bound), the ``completed+rejected+failed+expired == offered`` accounting
identity, and a goodput floor of ``chaos_goodput_floor`` x measured
capacity (both sides measured in the same run — noise-aware without a
separate margin). The §15 self-healing scenarios in the same artifact
are gated alongside: the dispatcher-kill run must show ``restarts >= 1``
with requeued requests, survival exactly 1.0, zero hung futures, and
the accounting identity intact across the restart; the corrupt-reload
run must fail typed with the old plan still serving and the step
walk-back recovering; the kernel-degradation run must demote exactly
the faulty bucket (innocents bit-identical), re-promote after the heal,
and hold ``selfheal_goodput_floor`` x the healthy path's goodput.

``BENCH_lm.json`` gates the §13 LM datapath: compressed projection
GEMMs must not lose to the dense matmul the pre-PR-8 ``apply_linear``
fallback silently ran (``lm_wall_margin``, noise-widened like the fused
gate), and the frozen ``LM.plan()`` prefill must be bit-identical to —
and no slower than — the jitted unplanned forward. The int8 GEMM
numbers are recorded but not gated (XLA:CPU has no native int8 path).

Exit code 1 on any regression — run after ``python -m benchmarks.run
--smoke`` (which rewrites all three artifacts).
"""
from __future__ import annotations

import json
import math
import pathlib
import sys

ROOT = pathlib.Path(__file__).resolve().parents[1]
BASELINES = pathlib.Path(__file__).resolve().parent / "bench_baselines.json"
_BASE = json.loads(BASELINES.read_text())
TOLERANCE = 0.02   # absolute saved_frac slack for rounding
WALL_MARGIN = _BASE["fused_wall_margin"]
NOISE_CAP = _BASE["fused_noise_cap"]
HARD_FLOOR = 0.25  # the §9 acceptance criterion, regardless of baseline


# ---------------------------------------------------------------------------
# Artifact schemas: {dotted.path: check} where check is 'num' (finite > 0),
# 'frac' (finite ≥ 0), or a type. A path ending in '[]' descends into every
# element of a non-empty list.
# ---------------------------------------------------------------------------

SCHEMAS = {
    "BENCH_fused.json": {
        "layers[].name": str,
        "layers[].saved_frac": "frac",
        "layers[].hbm_bytes_fused": "num",
        "layers[].hbm_bytes_unfused": "num",
        "wall_time_us.layer_fused": "num",
        "wall_time_us.layer_unfused": "num",
        "wall_time_us.cnn_int8_resident": "num",
        "wall_time_us.cnn_per_layer_dequant": "num",
        "noise_frac.layer": "frac",
        "noise_frac.cnn": "frac",
        "harness.reps": "num",
        "harness.stat": str,
    },
    "BENCH_serve.json": {
        "plan_us": "num",
        "unplanned_jit_us": "num",
        "bit_identical": bool,
        "chaos.innocent_survival": "frac",
        "chaos.poison_typed": bool,
        "chaos.accounting_ok": bool,
        "overload.goodput_rps": "num",
        "overload.capacity_rps": "num",
        "overload.shed_rate": "frac",
        "overload.accounting_ok": bool,
        "overload.p99_us": "num",
        "overload.p99_bound_us": "num",
        "selfheal.restart.restarts": "num",
        "selfheal.restart.survival": "frac",
        "selfheal.restart.requeued": "num",
        "selfheal.restart.hung": "frac",
        "selfheal.restart.accounting_ok": bool,
        "selfheal.reload.corrupt_typed": bool,
        "selfheal.reload.old_plan_served": bool,
        "selfheal.reload.fallback_recovered": bool,
        "selfheal.reload.reloads": "num",
        "selfheal.degraded.survival": "frac",
        "selfheal.degraded.demoted_exact": bool,
        "selfheal.degraded.innocents_bit_identical": bool,
        "selfheal.degraded.repromoted": bool,
        "selfheal.degraded.healthy_sps": "num",
        "selfheal.degraded.degraded_sps": "num",
        "selfheal.degraded.accounting_ok": bool,
    },
    "BENCH_lm.json": {
        "gemms[].name": str,
        "gemms[].dense_us": "num",
        "gemms[].compressed_us": "num",
        "gemms[].int8_us": "num",
        "plan.plan_us": "num",
        "plan.unplanned_us": "num",
        "plan.bit_identical": bool,
        "noise_frac.plan": "frac",
        "harness.reps": "num",
        "harness.stat": str,
    },
}


def _walk(data, parts):
    """Yield every value at a dotted path, descending lists at '[]'."""
    if not parts:
        yield data
        return
    head, rest = parts[0], parts[1:]
    if head.endswith("[]"):
        items = data.get(head[:-2], []) if isinstance(data, dict) else []
        if not isinstance(items, list) or not items:
            yield None  # an empty/missing list fails the leaf check below
            return
        for item in items:
            yield from _walk(item, rest)
    else:
        yield from _walk(data.get(head) if isinstance(data, dict) else None, rest)


def schema_errors(name: str, data) -> list:
    """Validate one artifact dict against its schema (see SCHEMAS)."""
    errors = []
    for path, check in SCHEMAS.get(name, {}).items():
        for v in _walk(data, path.split(".")):
            if check == "num":
                ok = isinstance(v, (int, float)) and not isinstance(v, bool) \
                    and math.isfinite(v) and v > 0
                want = "finite positive number"
            elif check == "frac":
                ok = isinstance(v, (int, float)) and not isinstance(v, bool) \
                    and math.isfinite(v) and v >= 0
                want = "finite non-negative number"
            else:
                ok = isinstance(v, check)
                want = check.__name__
            if not ok:
                errors.append(f"{name}: schema: {path} = {v!r} (want {want})")
    return errors


def _wall_margin(noise) -> float:
    """Self-calibrating gate margin: the committed ``fused_wall_margin``
    widened by the measured host noise of the same sample batch, capped so
    a pathologically noisy artifact cannot gate itself vacuously."""
    noise = noise if isinstance(noise, (int, float)) and math.isfinite(noise) \
        else NOISE_CAP
    return WALL_MARGIN * (1.0 + min(max(noise, 0.0), NOISE_CAP))


def check_fused() -> list:
    errors = []
    path = ROOT / "BENCH_fused.json"
    if not path.exists():
        return [f"{path.name} missing (run `python -m benchmarks.run --smoke`)"]
    fresh = json.loads(path.read_text())
    errors += schema_errors(path.name, fresh)
    if errors:
        return errors  # gates below would read garbage
    base = _BASE.get("fused_saved_frac", {})
    for layer in fresh.get("layers", []):
        name, saved = layer["name"], layer["saved_frac"]
        if saved < HARD_FLOOR:
            errors.append(f"fused/{name}: saved_frac {saved:.3f} < hard floor {HARD_FLOOR}")
        ref = base.get(name)
        if ref is not None and saved < ref - TOLERANCE:
            errors.append(
                f"fused/{name}: saved_frac regressed {ref:.3f} -> {saved:.3f} "
                f"(tolerance {TOLERANCE}; committed baseline {BASELINES.name})"
            )
    # measured-wall-time gates (§12): fused must not lose to unfused
    wall, noise = fresh["wall_time_us"], fresh["noise_frac"]
    pairs = (
        ("layer_fused", "layer_unfused", "layer"),
        ("cnn_int8_resident", "cnn_per_layer_dequant", "cnn"),
    )
    for fast, slow, nkey in pairs:
        margin = _wall_margin(noise.get(nkey))
        if wall[fast] > wall[slow] * margin:
            errors.append(
                f"fused/{fast}: {wall[fast]:.0f}us > {wall[slow]:.0f}us "
                f"({slow}) x margin {margin:.2f} (= fused_wall_margin "
                f"{WALL_MARGIN} widened by measured noise "
                f"{noise.get(nkey)})"
            )
    return errors


def check_serve() -> list:
    errors = []
    path = ROOT / "BENCH_serve.json"
    if not path.exists():
        return [f"{path.name} missing (run `python -m benchmarks.run --smoke`)"]
    data = json.loads(path.read_text())
    errors += schema_errors(path.name, data)
    if errors:
        return errors
    if not data.get("bit_identical", False):
        errors.append("serve: bucketed/padded serving not bit-identical to "
                      "per-request plan.serve")
    plan_us, unplanned_us = data.get("plan_us"), data.get("unplanned_jit_us")
    if plan_us is not None and unplanned_us is not None \
            and plan_us > unplanned_us * _BASE["serve_plan_margin"]:
        errors.append(  # the measured-wall-time contract (ROADMAP)
            f"serve: bucket plan {plan_us}us > jitted-once unplanned "
            f"{unplanned_us}us (margin {_BASE['serve_plan_margin']}x)"
        )
    for name, p in data.get("patterns", {}).items():
        if p.get("completed") != p.get("offered"):
            errors.append(f"serve/{name}: completed {p.get('completed')} != "
                          f"offered {p.get('offered')}")
        if p.get("retraces_after_warmup", 1) != 0:
            errors.append(f"serve/{name}: "
                          f"{p.get('retraces_after_warmup')} retraces under "
                          "load (bucketed plans must serve retrace-free)")
        p99, bound = p.get("p99_us"), p.get("p99_bound_us")
        if p99 is not None and bound is not None and p99 > bound:
            errors.append(f"serve/{name}: p99 {p99}us > self-calibrated "
                          f"bound {bound}us")
    if not data.get("patterns"):
        errors.append("serve: no load patterns recorded")
    return errors


def check_chaos() -> list:
    """Gate the §14 robustness scenarios recorded in BENCH_serve.json:
    blast-radius isolation (every innocent in a poisoned co-batch must
    have completed bit-identical — survival exactly 1.0 — with the
    poisons typed-failed and zero bisect retraces) and overload shedding
    (books balanced, shed under 2x capacity, admitted p99 within its
    self-calibrated bound, goodput above ``chaos_goodput_floor`` x
    measured capacity — noise-aware by construction: both sides of the
    ratio are measured on the same host in the same run)."""
    errors = []
    path = ROOT / "BENCH_serve.json"
    if not path.exists():
        return []  # check_serve already reports the missing artifact
    data = json.loads(path.read_text())
    chaos, over = data.get("chaos"), data.get("overload")
    if not chaos or not over:
        return ["serve: chaos/overload scenarios missing from "
                f"{path.name} (stale artifact? rerun benchmarks)"]
    if chaos.get("innocent_survival") != 1.0:
        errors.append(
            f"chaos: innocent survival {chaos.get('innocent_survival')} != "
            "1.0 — a poisoned co-batch damaged innocent requests")
    if not chaos.get("poison_typed", False):
        errors.append("chaos: poison futures did not fail with their typed "
                      "exceptions (FaultInjected / NumericalFault)")
    if chaos.get("retraces_after_warmup", 1) != 0:
        errors.append(f"chaos: bisect isolation retraced "
                      f"{chaos.get('retraces_after_warmup')}x (halves must "
                      "land on warmed buckets)")
    for name, d in (("chaos", chaos), ("overload", over)):
        if not d.get("accounting_ok", False):
            errors.append(f"{name}: completed+rejected+failed+expired != "
                          "offered (requests leaked)")
    if not over.get("shed_rate", 0) > 0:
        errors.append("overload: 2x capacity offered but nothing shed "
                      "(admission control inert)")
    p99, bound = over.get("p99_us"), over.get("p99_bound_us")
    if p99 is not None and bound is not None and p99 > bound:
        errors.append(f"overload: admitted p99 {p99}us > bounded-queue "
                      f"bound {bound}us")
    floor = _BASE["chaos_goodput_floor"] * over.get("capacity_rps", 0)
    if over.get("goodput_rps", 0) < floor:
        errors.append(
            f"overload: goodput {over.get('goodput_rps')} rps < "
            f"{_BASE['chaos_goodput_floor']} x capacity "
            f"{over.get('capacity_rps')} rps — shedding collapsed service")
    errors += _check_selfheal(data)
    return errors


def _check_selfheal(data) -> list:
    """Gate the §15 self-healing scenarios recorded in BENCH_serve.json:
    the dispatcher-kill run must actually have gone through supervision
    (``restarts >= 1`` with requests requeued), every request must have
    completed bit-identical (survival exactly 1.0, zero hung futures)
    with the accounting identity spanning the restart; a corrupt
    checkpoint must have failed typed with the old plan still serving
    and the step walk-back recovering; and the kernel-degradation run
    must have demoted exactly the faulty bucket (innocents
    bit-identical), re-promoted after the heal, and sustained
    ``selfheal_goodput_floor`` x the healthy path's goodput."""
    errors = []
    sh = data.get("selfheal")
    if not sh:
        return ["serve: selfheal scenarios missing from BENCH_serve.json "
                "(stale artifact? rerun benchmarks)"]
    r = sh.get("restart", {})
    if not r.get("restarts", 0) >= 1:
        errors.append("selfheal/restart: restarts == 0 — the kill never "
                      "exercised supervision")
    if not r.get("requeued", 0) >= 1:
        errors.append("selfheal/restart: nothing requeued across the "
                      "restart (at-most-once handoff inert)")
    if r.get("survival") != 1.0 or r.get("hung", 1) != 0:
        errors.append(
            f"selfheal/restart: survival {r.get('survival')} with "
            f"{r.get('hung')} hung futures (want 1.0 with 0) — the "
            "restart dropped or stranded requests")
    if not r.get("accounting_ok", False):
        errors.append("selfheal/restart: accounting identity broke across "
                      "the supervised restart")
    rl = sh.get("reload", {})
    if not rl.get("corrupt_typed", False):
        errors.append("selfheal/reload: corrupt checkpoint did not fail "
                      "with typed CorruptCheckpointError")
    if not rl.get("old_plan_served", False):
        errors.append("selfheal/reload: old plan not serving bit-identical "
                      "after the failed reload")
    if not rl.get("fallback_recovered", False):
        errors.append("selfheal/reload: step walk-back did not recover a "
                      "verifiable checkpoint")
    d = sh.get("degraded", {})
    if d.get("survival") != 1.0:
        errors.append(f"selfheal/degraded: survival {d.get('survival')} != "
                      "1.0 — demotion dropped requests")
    if not d.get("demoted_exact", False) \
            or not d.get("innocents_bit_identical", False):
        errors.append("selfheal/degraded: demotion was not isolated to "
                      "exactly the faulty bucket with innocent buckets "
                      "bit-identical")
    if not d.get("repromoted", False):
        errors.append("selfheal/degraded: recovery probe never re-promoted "
                      "the healed bucket")
    floor = _BASE["selfheal_goodput_floor"] * d.get("healthy_sps", 0)
    if d.get("degraded_sps", 0) < floor:
        errors.append(
            f"selfheal/degraded: goodput {d.get('degraded_sps')} < "
            f"{_BASE['selfheal_goodput_floor']} x healthy "
            f"{d.get('healthy_sps')} samples/s — fallback collapsed")
    return errors


def check_lm() -> list:
    errors = []
    path = ROOT / "BENCH_lm.json"
    if not path.exists():
        return [f"{path.name} missing (run `python -m benchmarks.run --smoke`)"]
    data = json.loads(path.read_text())
    errors += schema_errors(path.name, data)
    if errors:
        return errors
    noise = data.get("noise_frac", {})
    # the §13 contract: compressed projections must not lose to the dense
    # matmul the pre-PR-8 fallback silently ran (nnz/bz of the MACs)
    margin_base = _BASE["lm_wall_margin"]
    cap = _BASE["lm_noise_cap"]
    for g in data.get("gemms", []):
        nz = noise.get(g["name"])
        nz = nz if isinstance(nz, (int, float)) and math.isfinite(nz) else cap
        margin = margin_base * (1.0 + min(max(nz, 0.0), cap))
        if g["compressed_us"] > g["dense_us"] * margin:
            errors.append(
                f"lm/{g['name']}: compressed {g['compressed_us']:.0f}us > "
                f"dense {g['dense_us']:.0f}us x margin {margin:.2f} "
                f"(= lm_wall_margin {margin_base} widened by noise {nz})"
            )
    plan = data.get("plan") or {}
    if not plan.get("bit_identical", False):
        errors.append("lm/plan: frozen plan not bit-identical to the "
                      "unplanned forward")
    nz = noise.get("plan")
    nz = nz if isinstance(nz, (int, float)) and math.isfinite(nz) else cap
    margin = margin_base * (1.0 + min(max(nz, 0.0), cap))
    if plan and plan["plan_us"] > plan["unplanned_us"] * margin:
        errors.append(
            f"lm/plan: plan {plan['plan_us']:.0f}us > unplanned "
            f"{plan['unplanned_us']:.0f}us x margin {margin:.2f}"
        )
    return errors


def main() -> int:
    errors = check_fused() + check_serve() + check_chaos() + check_lm()
    for e in errors:
        print(f"REGRESSION: {e}", file=sys.stderr)
    if not errors:
        print("benchmark artifacts: no regressions")
    return 1 if errors else 0


if __name__ == "__main__":
    sys.exit(main())
