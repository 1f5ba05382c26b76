"""Benchmark harness — one module per paper table/figure.

Prints ``name,us_per_call,derived`` CSV. ``python -m benchmarks.run``.
``--smoke`` runs the fast analytic subset (what CI runs so benchmark
modules can't silently rot); the interpret-mode Pallas sweeps stay out.
``--json <path>`` additionally writes every reported row as JSON for
trajectory tracking (CI uploads the smoke results as an artifact).

Every sub-benchmark failure is caught, reported inline, and re-listed in
a ``FAILED n/m`` summary at the end; the process exits 1 if *any* module
failed (not just the last one), so CI cannot green-wash a mid-run
assertion. ``REPRO_BENCH_EXTRA`` (colon-separated module names) appends
extra bench modules — the hook the subprocess test uses to prove the
exit-code contract with a deliberately failing module.
"""
from __future__ import annotations

import argparse
import json
import os
import pathlib
import sys
import traceback

# Runnable from a bare checkout: put src/ on the path (mirrors
# tests/conftest.py, so CI needs no PYTHONPATH plumbing).
_SRC = str(pathlib.Path(__file__).resolve().parents[1] / "src")
if _SRC not in sys.path:
    sys.path.insert(0, _SRC)


def report(name: str, us_per_call: float, derived: str = ""):
    print(f"{name},{us_per_call:.1f},{derived}")


# (label, module, in the --smoke subset)
BENCHES = [
    ("table_v (Table V headline TOPS/W)", "benchmarks.bench_table_v", True),
    ("design_space (Fig 9/10)", "benchmarks.bench_design_space", True),
    ("sparsity_scaling (Fig 12)", "benchmarks.bench_sparsity_scaling", True),
    ("dbb_pruning (Table I/II)", "benchmarks.bench_dbb_pruning", False),
    ("im2col (IM2COL unit, Fig 8)", "benchmarks.bench_im2col", False),
    ("sparse_conv (IM2COL x VDBB fused)", "benchmarks.bench_sparse_conv", False),
    ("kernels (VDBB matmul)", "benchmarks.bench_kernels", False),
    ("quant (INT8 datapath, DESIGN §8)", "benchmarks.bench_quant", True),
    ("fused (epilogue fusion, DESIGN §9)", "benchmarks.bench_fused", True),
    ("serve (continuous-batching tier + chaos, DESIGN §11/§14)", "benchmarks.bench_serve", True),
    ("lm (LM VDBB routing + plans, DESIGN §13)", "benchmarks.bench_lm", True),
    ("roofline (EXPERIMENTS §Roofline)", "benchmarks.roofline", True),
]


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None, help="substring filter")
    ap.add_argument(
        "--smoke", action="store_true",
        help="fast analytic subset (CI): energy model + measured-act benches",
    )
    ap.add_argument(
        "--json", default=None, metavar="PATH",
        help="also write every reported row as JSON (trajectory tracking)",
    )
    args = ap.parse_args()
    print("name,us_per_call,derived")
    failures = []
    rows = []

    def record(name: str, us_per_call: float, derived: str = ""):
        report(name, us_per_call, derived)
        rows.append(dict(name=name, us_per_call=us_per_call, derived=derived))

    import importlib

    benches = list(BENCHES)
    extra = os.environ.get("REPRO_BENCH_EXTRA", "")
    benches += [(m, m, True) for m in extra.split(":") if m]
    ran = 0
    for label, mod, smoke_ok in benches:
        if args.only and args.only not in mod:
            continue
        if args.smoke and not smoke_ok:
            continue
        ran += 1
        try:
            importlib.import_module(mod).run(record)
        except Exception as e:  # noqa: BLE001
            failures.append((label, e))
            traceback.print_exc()
            record(f"{mod}/FAILED", 0.0, f"{type(e).__name__}: {e}")
    if args.json:
        pathlib.Path(args.json).write_text(json.dumps({"rows": rows}, indent=2))
    if failures:
        print(f"FAILED {len(failures)}/{ran} benchmarks:", file=sys.stderr)
        for label, e in failures:
            print(f"  {label}: {type(e).__name__}: {e}", file=sys.stderr)
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
