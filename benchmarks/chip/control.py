"""The control of the comparison that decides ``correct``: the plain
reference in the system's place, one precision below the configuration's.

    python3 benchmarks/chip/control.py --workload <cell> --seeds 1 2 3

For each seed: the cell's weights, calibration images and request pool,
exactly as a run of that seed makes them; the reference's float32 logits
of every pool image; and the control's — compressed layers' weights and
activations rounded to int4 (the configuration serves int8) and the fp32
stem in bfloat16. The control's logits go, as the answers of one window
covering every pool image (every answer a run can serve), through the
harness's own comparison (``check.decide``), which has to decide
``correct`` false. Prints, per seed, each number compared beside its limit
and the decision; exits 1 if any seed's control comes out correct. The
benchmark's own runs never run this.
"""
from __future__ import annotations

import argparse
import json
import sys

import numpy as np

import check
import run as harness
import traffic as traffic_mod


def reading(jax, config: dict, traffic: dict, family, reference, seed: int) -> tuple:
    """``check.decide``'s (correct, checks) for the control's answers."""
    k_w, k_cal, k_pool = jax.random.split(harness.seed_key(jax, seed), 3)
    weights = jax.jit(lambda k: reference.init_weights(config, k))(k_w)
    shape = (config["image_size"], config["image_size"], config["in_channels"])
    x_cal = jax.random.normal(k_cal, (config["calibration_images"], *shape))
    amax = reference.calibrate(config, weights, x_cal)
    pool = family.make_inputs(config, k_pool, traffic["pool_images"])
    win = traffic_mod.Window()
    ref = []
    for i in range(0, len(pool), harness.REF_BLOCK):
        x = pool[i:i + harness.REF_BLOCK]
        ref.append(np.asarray(reference.forward(config, weights, x)))
        win.answers[len(win.start)] = np.asarray(
            reference.forward(config, weights, x, bits=4, amax=amax))
        win.start.append(i)
        win.n.append(len(x))
    return check.decide(win, np.concatenate(ref), config["limits"])


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)
    spec = harness.read_json(harness.ROOT / "BENCHMARK.json")
    cell, entry = harness.cell_of(spec, args.workload)
    config = harness.read_json(harness.ROOT / entry["file"])
    traffic = harness.read_json(harness.BENCH / "traffic" / f"{cell['traffic']}.json")
    family = harness.load(harness.BENCH / "families" / f"{config['family']}.py", "fam")
    reference = harness.load(harness.BENCH / "references" / f"{config['reference']}.py", "ref")
    import jax

    passed = []
    for seed in args.seeds:
        correct, checks = reading(jax, config, traffic, family, reference, seed)
        print(json.dumps({"workload": args.workload, "seed": seed, "correct": correct,
                          "checks": checks}), flush=True)
        if correct:
            passed.append(seed)
    if passed:
        print(f"[control] came out correct on seeds {passed}", file=sys.stderr)
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
