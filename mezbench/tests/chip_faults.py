"""Read a planted fault's numbers on the chip, at the cell's own size: one
process runs the cell once per seed with the fault in place and prints
each run's result line.

    python3 mezbench/tests/chip_faults.py <fault> <workload> <seconds> \
        <seed> [<seed> ...]

Faults: ``label_one_round_short`` (the device labeler one propagation
round short of its fixed point), ``blank_payloads`` (every frame served
blank), and ``control`` (no fault: the run with ``--control 1``).
"""

from __future__ import annotations

import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
for _p in (ROOT, os.path.join(ROOT, "src")):
    if _p not in sys.path:
        sys.path.insert(0, _p)

from mezbench import run  # noqa: E402
from mezbench.tests import faults  # noqa: E402

FAULTS = {"label_one_round_short": faults.plant_label_one_round_short,
          "blank_payloads": faults.plant_blank_payloads,
          "control": None}


def main(argv) -> int:
    fault, workload, seconds, *seeds = argv
    rc = 0
    for seed in seeds:
        print(f"== {fault} {workload} seed={seed}", flush=True)
        extra = ["--control", "1"] if fault == "control" else []
        rc |= run.main(["--workload", workload, "--seed", seed,
                        "--seconds", seconds, "--trace", "0", *extra],
                       t_start=time.perf_counter(), patch=FAULTS[fault])
    return rc


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
