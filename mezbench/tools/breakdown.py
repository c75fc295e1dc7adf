"""Run one benchmark cell traced and print where its host time went, from
the program's own spans (``mez.*``, read by ``mezbench/spans.py``).

    python3 mezbench/tools/breakdown.py --workload <name> --seed <n> \\
        --seconds <s>

The cell runs as ``run.py --trace 1`` runs it: the same set-up, warm-up
and profiled window, without the check against the reference.  The last
line of standard output is one JSON object: the device, the cell's
per-layer metrics through their own readers, ``spans.layer_numbers``,
every program span's ``[seconds, count, self seconds, [parents]]``, and
the device's ten longest idle gaps labelled ``mezbench.<x>/mez.<y>``.  On
a program with no spans those read empty, and the gaps keep the
benchmark's labels.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import importlib  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

for _v in ("OMP_NUM_THREADS", "OPENBLAS_NUM_THREADS", "MKL_NUM_THREADS"):
    os.environ.setdefault(_v, "1")

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
for _p in (ROOT, os.path.join(ROOT, "src")):
    if _p not in sys.path:
        sys.path.insert(0, _p)

from mezbench import harness, spans  # noqa: E402


class _Window(harness.Window):
    """The harness's profiled window; its trace is also reduced to the
    program's spans before it is deleted."""

    spans = None

    def reduce(self):
        if self._dir is not None:
            self.spans = spans.reduce_dir(self._dir.name)
        return super().reduce()


def main(argv=None, *, require_tpu: bool = True, t_start: float = None,
         patch=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    cell = harness.load_cell(args.workload, args.seed, args.seconds, True)
    harness.enable_compile_cache()
    try:
        device = harness.check_device(int(cell.workload["chips"]),
                                      require_tpu)
    except harness.NoChip as e:
        print(f"breakdown: {e}", file=sys.stderr)
        return 3
    if patch is not None:
        patch(cell)
    runner = importlib.import_module("mezbench." + cell.traffic["runner"])
    run, state = runner.run(cell, lambda: _Window(True),
                            T_START if t_start is None else t_start)
    win = state["win"]
    run.summary = win.reduce()
    found = win.spans
    print(json.dumps({
        "workload": cell.name, "seed": cell.seed, "device": device,
        "busy_s": run.summary.busy_s, "window_s": run.summary.window_s,
        "metrics": harness.read_metrics(cell, run),
        "layers": spans.layer_numbers(found, run),
        "spans": found.table(), "gaps": found.gaps}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
