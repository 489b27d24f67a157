"""Run one benchmark cell once on the chip this process holds.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell (``BENCHMARK.json`` ``workloads``) names a configuration and a
traffic mix; the run builds them from the seed, warms up, measures for
``--seconds`` and prints one JSON result line last on standard output.  It
exits non-zero, printing no result, where JAX finds no TPU or fewer chips
than the cell asks for.
"""
import time

T0 = time.perf_counter()

import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
# The checkout root (for ``bench.*``) and the program's sources take the
# place of this script's own directory, whose module names (``trace``) would
# shadow the standard library's.
sys.path[0:1] = [str(ROOT), str(ROOT / "src")]

if __name__ == "__main__":
    from bench import harness
    sys.exit(harness.main(t0=T0))
