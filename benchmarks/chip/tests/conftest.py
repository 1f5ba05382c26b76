"""The benchmark's own checks: ``python3 -m pytest benchmarks/chip/tests``
from the root of the checkout (they run on the CPU; the repo's own tests do
not collect them)."""
import pathlib
import sys

BENCH = pathlib.Path(__file__).resolve().parents[1]
ROOT = BENCH.parents[1]
sys.path[:0] = [str(BENCH), str(BENCH / "metrics"), str(ROOT / "src")]
