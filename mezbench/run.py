"""Run one benchmark cell once and print its result line.

    python3 mezbench/run.py --workload <name> --seed <n> --seconds <s> \\
        --trace <0|1> [--control 1]

The cell (``BENCHMARK.json``'s ``workloads`` entry), its configuration
(``configs/<config>.json``) and its traffic (``traffic/<traffic>.json``)
are found by name; the traffic's ``runner`` names the module that runs it
(``<runner>.py`` beside this file, with ``run`` and ``check``) and the
limits its check is held to (``limits/<runner>.json``).  The run checks
the device first and exits non-zero, with no
result, when JAX finds no TPU or fewer chips than the cell asks for.  It
then sets up (inputs drawn from ``--seed``, the program's own
characterization, warm-up of every shape the window uses), measures for
``--seconds``, checks what the window served against the plain reference
under ``reference/``, and prints one JSON line.  With ``--trace 1`` the
window runs under the profiler and the line carries the per-layer metrics.

``--control 1`` adds the control's readings: the check run against a
reference computed in lower precision (serving cells, ``control.*``), or
the whole window run on the program's own XLA transform path in place of
the exact kernel (onboarding cells).  Benchmark runs never pass it.

Threads: the run keeps BLAS and OpenMP pools to one thread each, so that
its load comes from one process with few threads.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import importlib  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

for _v in ("OMP_NUM_THREADS", "OPENBLAS_NUM_THREADS", "MKL_NUM_THREADS"):
    os.environ.setdefault(_v, "1")

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
for _p in (ROOT, os.path.join(ROOT, "src")):
    if _p not in sys.path:
        sys.path.insert(0, _p)


def _limits(runner: str) -> dict:
    """``{name: {"max": x} or {"min": x}}`` for the runner's check."""
    with open(os.path.join(HERE, "limits", runner + ".json")) as fh:
        return json.load(fh)


def _entry(value, limit: dict) -> dict:
    """A compared number beside its limit, as the result line shows it."""
    if "max" in limit:
        return {"value": value, "limit": limit["max"], "keep": "at most"}
    return {"value": value, "limit": limit["min"], "keep": "at least"}


def _within(value, limit: dict) -> bool:
    if value is None or (isinstance(value, float) and math.isnan(value)):
        return False
    if "max" in limit:
        return value <= limit["max"]
    return value >= limit["min"]


def main(argv=None, *, require_tpu: bool = True, t_start: float = None,
         patch=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--control", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    t_start = T_START if t_start is None else t_start
    if not os.path.isdir(os.path.join(ROOT, "src", "repro")):
        print("mezbench: the program (src/repro) is not in this checkout",
              file=sys.stderr)
        return 2

    from mezbench import harness

    cell = harness.load_cell(args.workload, args.seed, args.seconds,
                             bool(args.trace))
    harness.enable_compile_cache()
    try:
        device = harness.check_device(int(cell.workload["chips"]),
                                      require_tpu)
    except harness.NoChip as e:
        print(f"mezbench: {e}", file=sys.stderr)
        return 3
    if patch is not None:
        patch(cell)
    compiles = harness.CompileCounter()

    def window():
        w = harness.Window(cell.trace)
        compiles.armed = True
        return w

    runner = importlib.import_module("mezbench." + cell.traffic["runner"])
    run, state = runner.run(cell, window, t_start, control=bool(args.control))
    compiles.armed = False
    device["memory_peak_bytes"] = harness.memory_peak_bytes()
    run.summary = state["win"].reduce()
    if run.summary is not None:
        device["busy_s"] = run.summary.busy_s
        device["window_s"] = run.summary.window_s

    numbers = runner.check(state)
    limits = _limits(cell.traffic["runner"])
    checks = {k: _entry(numbers[k], limits[k]) for k in limits}
    correct = all(_within(numbers[k], limits[k]) for k in limits)
    for k in limits:
        if "control." + k in numbers:
            checks["control." + k] = _entry(numbers["control." + k],
                                            limits[k])
    print(f"mezbench: {cell.name} seed={cell.seed} window={run.window_s:.3f}s"
          f" compiles_in_window={compiles.count} extra={json.dumps(numbers)}",
          file=sys.stderr, flush=True)
    result = {"correct": bool(correct), "attempted": int(run.attempted),
              "failed": int(run.failed),
              "metrics": harness.read_metrics(cell, run), "device": device}
    if run.summary is not None:
        result["breakdown"] = run.summary.breakdown()
    harness.emit(result, checks)
    return 0


if __name__ == "__main__":
    sys.exit(main())
