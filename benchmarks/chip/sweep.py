"""Find an open-loop cell's knee on the chip: one server, several rates.

    python3 benchmarks/chip/sweep.py --workload <cell> --rates 2000 3000 4000 \\
        [--seconds 10] [--seed 1]

Builds the cell's system once, then offers its traffic mix at each rate in
turn for ``--seconds``. Per rate it prints requests sent, the share that
completed inside the window, how long past the close the last one took,
p50/p99 latency (from the time each request was due) and the generator's
p99 lateness. The knee is the highest rate at which completions keep pace
with sends: nearly all complete inside the window and the last one lands
within a few batch times of the close. The benchmark's own runs never
search for a rate; this is how the rate in a traffic file was found.
"""
from __future__ import annotations

import argparse
import json
import sys
import types

import run as harness


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", type=float, nargs="+", required=True)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--seed", type=int, default=1)
    args = ap.parse_args(argv)
    spec = harness.read_json(harness.ROOT / "BENCHMARK.json")
    cell, entry = harness.cell_of(spec, args.workload)
    config = harness.read_json(harness.ROOT / entry["file"])
    traffic = harness.read_json(harness.BENCH / "traffic" / f"{cell['traffic']}.json")
    if traffic["loop"] != "open":
        raise SystemExit(f"{args.workload} is not an open loop")
    sys.path[:0] = [str(harness.ROOT / "src"), str(harness.BENCH),
                    str(harness.BENCH / "metrics")]
    import jax

    jax.config.update("jax_compilation_cache_dir", str(harness.CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    import _latency
    import gen_lag_p99_ms
    import traffic as traffic_mod

    family = harness.load(harness.BENCH / "families" / f"{config['family']}.py", "fam")
    reference = harness.load(harness.BENCH / "references" / f"{config['reference']}.py", "ref")
    devices, peaks = harness.device_info(jax, cell["chips"], harness.read_json(
        harness.BENCH / "peaks.json"))
    k_w, k_cal, k_pool = jax.random.split(harness.seed_key(jax, args.seed), 3)
    system = family.build(config, traffic, {"weights": k_w, "calibration": k_cal},
                          devices, reference, {})
    pool = family.make_inputs(config, k_pool, traffic["pool_images"])
    try:
        for i, rate in enumerate(args.rates):
            t = dict(traffic, rate_rps=rate)
            win = traffic_mod.run(system, t, pool, args.seconds, args.seed + i)
            r = types.SimpleNamespace(window=win)
            inside = sum(1 for k, d in win.done.items() if d <= win.t_end)
            last = max(win.done.values()) - win.t_end if win.done else None
            print(json.dumps({
                "rate_rps": rate, "sent": len(win.n), "failed": len(win.error),
                "completed_in_window": inside / max(1, len(win.n)),
                "last_done_after_close_s": last,
                "p50_ms": _latency.percentile(r, 50), "p99_ms": _latency.percentile(r, 99),
                "gen_lag_p99_ms": gen_lag_p99_ms.read(r),
                "batches": system.stats.batches,
            }), flush=True)
    finally:
        system.stop()
    return 0


if __name__ == "__main__":
    sys.exit(main())
