"""Run a list of benchmark commands one after another, each in its own
process, and keep each one's output as ``<out_dir>/<tag>.log``.

    python3 mezbench/tools/batch.py <plan.json> <out_dir> [budget_s]

The plan is a JSON list of ``[tag, [argv...], timeout_s]``; no run starts
once ``budget_s`` seconds have passed.  This parent never imports JAX, so
each child has the chip to itself.
"""

import json
import os
import subprocess
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def main() -> int:
    with open(sys.argv[1]) as fh:
        plan = json.load(fh)
    out = os.path.join(ROOT, sys.argv[2])
    budget = float(sys.argv[3]) if len(sys.argv) > 3 else float("inf")
    os.makedirs(out, exist_ok=True)
    start = time.time()
    for tag, argv, timeout in plan:
        t0 = time.time()
        if t0 - start > budget:
            print(f"== {tag} skipped: budget spent", flush=True)
            continue
        with open(os.path.join(out, tag + ".log"), "w") as log:
            try:
                p = subprocess.run(argv, cwd=ROOT, stdout=log,
                                   stderr=subprocess.STDOUT, timeout=timeout)
                rc = p.returncode
            except subprocess.TimeoutExpired:
                rc = "timeout"
        last = ""
        with open(os.path.join(out, tag + ".log")) as log:
            lines = [ln for ln in log.read().splitlines() if ln.strip()]
        for ln in reversed(lines):
            if ln.startswith("{"):
                last = ln
                break
        checks = [ln for ln in lines if ln.startswith("check ")]
        print(f"== {tag} rc={rc} {time.time() - t0:.1f}s", flush=True)
        for ln in checks:
            print("   " + ln, flush=True)
        print("   " + last[:1500], flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
