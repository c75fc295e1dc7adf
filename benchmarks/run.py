"""Benchmark driver: one function per paper table/figure + beyond-paper.

Prints ``name,us_per_call,derived`` CSV per experiment and writes JSON
artifacts to results/bench/.  The roofline/dry-run sweeps are separate
(launch/dryrun.py, benchmarks/roofline.py) since they need the 512-device
XLA flag set before jax import.

Usage: PYTHONPATH=src:. python -m benchmarks.run [--only substr]
"""

from __future__ import annotations

import argparse
import sys
import traceback

from repro.compile_cache import enable_compile_cache


def main() -> None:
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None,
                    help="run only experiments whose name contains this")
    args = ap.parse_args()

    from benchmarks import approx, compute, paper

    experiments = [
        paper.table1_node_scaling,
        paper.table2_fps_distance,
        paper.fig5_latency_vs_size,
        paper.fig6_accuracy_vs_size,
        paper.fig11_controller_response,
        paper.fig12_e2e_latency_accuracy,
        paper.table3_controller_summary,
        paper.fig13_14_mez_vs_nats,
        paper.fig15_subscriber_scaling,
        paper.fig16_latency_breakdown,
        compute.fig17_compute_latency,
        compute.log_throughput,
        compute.knob_pipeline_cost,
        approx.approx_collectives,
        approx.compressed_training_quality,
    ]
    failures = 0
    for fn in experiments:
        if args.only and args.only not in fn.__name__:
            continue
        try:
            fn()
        except Exception:  # noqa: BLE001 - report and continue
            failures += 1
            print(f"{fn.__name__},nan,FAILED", flush=True)
            traceback.print_exc()
    if failures:
        sys.exit(1)


if __name__ == "__main__":
    main()
