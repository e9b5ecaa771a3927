"""Run one cell of the benchmark on the chip this process finds.

    python3 bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

The last line of standard output is the run's result as one JSON object;
the numbers the correctness check compared, each beside its limit, are the
last lines of standard error. Without a TPU, or with fewer chips than the
cell asks for, it exits with code 2 and prints no result.
"""

import pathlib
import sys
import time

T_PROCESS = time.perf_counter()
ROOT = pathlib.Path(__file__).resolve().parents[1]
# the checkout and the program's sources, in place of this script's own
# directory (whose module names must not shadow the standard library's)
sys.path[0:1] = [str(ROOT), str(ROOT / "src")]

from bench import harness  # noqa: E402

if __name__ == "__main__":
    sys.exit(harness.main(t_process=T_PROCESS))
