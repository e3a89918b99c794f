"""Benchmark entry point: seconds per `repro.dse.Study` on one chip.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Run from the root of a checkout on a machine with a TPU.  The last line of
standard output is one JSON object (`correct`, `attempted`, `failed`,
`metrics`, `device`, with `--trace 1` also `breakdown`, and last `checks`:
each compared number beside its limit).  Without the chips the cell asks
for it exits 1 and prints no result.  See `bench/harness.py`.
"""

import time

PROCESS_START = time.perf_counter()

import sys                                          # noqa: E402
from pathlib import Path                            # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
# the checkout root (for `bench`) and `src` (for `repro`) replace this
# script's own directory, whose module names are not meant to be global
sys.path[0:1] = [str(ROOT), str(ROOT / "src")]

from bench import harness                           # noqa: E402

if __name__ == "__main__":
    sys.exit(harness.main(process_start=PROCESS_START))
