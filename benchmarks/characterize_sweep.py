"""Characterization sweep benchmark: batched grid engine vs the per-setting
reference path, with and without knob4 (artifact removal).

Measures wall clock for a full knob-grid characterization on the standard
calibration clip with both engines, plus the wire-size proxy's calibration
error and the batched/reference kept-set agreement, and records the perf
trajectory in ``BENCH_characterize.json`` at the repo root (also mirrored
into the results dir).  Run by CI on every push; the committed
``benchmarks/baseline_characterize.json`` plus ``check_regression.py`` turn
it into a merge gate (speedup must not drop >20%, proxy error must stay
under 5%, engines must keep agreeing).

  PYTHONPATH=src python -m benchmarks.characterize_sweep [--clip-len 24]
"""

from __future__ import annotations

import argparse
import json
import os
import time

import numpy as np

from benchmarks.common import RESULTS_DIR, camera_factory, emit, ensure_dir
from repro.compile_cache import enable_compile_cache
from repro.core import grid_engine
from repro.core import knobs as K
from repro.core.characterization import characterize

ROOT_OUT = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "BENCH_characterize.json")


def main() -> None:
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--clip-len", type=int, default=24,
                    help="standard calibration clip length (frames)")
    ap.add_argument("--dynamics", default="complex")
    ap.add_argument("--seed", type=int, default=7)
    ap.add_argument("--repeats", type=int, default=3,
                    help="measured runs per engine; best-of-N is reported "
                         "(shared CI runners are noisy)")
    args = ap.parse_args()

    camf = camera_factory(args.dynamics, args.seed)
    n_settings = len(K.enumerate_settings())

    def best_of(engine: str, n: int, *, artifact: bool = False
                ) -> tuple[float, object]:
        times, table = [], None
        for _ in range(n):
            t0 = time.monotonic()
            table = characterize(camf, clip_len=args.clip_len, engine=engine,
                                 include_artifact=artifact)
            times.append(time.monotonic() - t0)
        return min(times), table

    t0 = time.monotonic()
    table_cold = characterize(camf, clip_len=args.clip_len, engine="batched")
    cold = time.monotonic() - t0

    batched, table_b = best_of("batched", args.repeats)
    reference, table_r = best_of("reference", max(1, args.repeats - 1))

    # knob4 on device: the batched engine now covers include_artifact=True
    # (3x the settings grid); the seed path for the same grid is the
    # per-frame reference sweep
    batched_art, table_ba = best_of("batched", max(1, args.repeats - 1),
                                    artifact=True)
    reference_art, table_ra = best_of("reference", 1, artifact=True)

    # proxy calibration quality on the same clip
    cam = camf()
    bg = cam.background
    clip = [cam.next_frame()[1] for _ in range(args.clip_len)]
    grid = grid_engine.run_grid(bg, clip)

    def agreement(tb, tr):
        kept_b, kept_r = set(tb.settings), set(tr.settings)
        shared = kept_b & kept_r
        acc_b = dict(zip(tb.settings, tb.acc_by_setting))
        acc_r = dict(zip(tr.settings, tr.acc_by_setting))
        acc_max_diff = max((abs(acc_b[s] - acc_r[s]) for s in shared),
                           default=0.0)
        return kept_b, kept_r, shared, acc_max_diff

    kept_b, kept_r, shared, acc_max_diff = agreement(table_b, table_r)
    kept_ba, kept_ra, shared_a, acc_max_diff_a = agreement(table_ba, table_ra)
    n_settings_art = len(K.enumerate_settings(include_artifact=True))

    payload = {
        "clip_len": args.clip_len,
        "dynamics": args.dynamics,
        "n_settings": n_settings,
        "batched_seconds_cold": round(cold, 3),
        "batched_seconds": round(batched, 3),
        "reference_seconds": round(reference, 3),
        "speedup_vs_seed_path": round(reference / batched, 2),
        "settings_per_second_batched": round(n_settings / batched, 1),
        "settings_per_second_reference": round(n_settings / reference, 1),
        "proxy_median_rel_err": round(grid.proxy.median_rel_err, 4),
        "proxy_max_rel_err": round(grid.proxy.max_rel_err, 4),
        "zlib_calls_batched": grid.zlib_calls,
        "zlib_calls_reference": n_settings // len(K.DIFF_THRESHOLDS)
        * args.clip_len,
        "kept_settings_batched": len(kept_b),
        "kept_settings_reference": len(kept_r),
        "kept_overlap": len(shared),
        "acc_max_diff_on_shared": round(float(acc_max_diff), 4),
        "settings_cold_equals_warm": table_cold.settings == table_b.settings,
        # knob4-included sweep (the PR 3 device-side coverage)
        "n_settings_art": n_settings_art,
        "batched_seconds_art": round(batched_art, 3),
        "reference_seconds_art": round(reference_art, 3),
        "speedup_with_artifact": round(reference_art / batched_art, 2),
        "kept_settings_batched_art": len(kept_ba),
        "kept_settings_reference_art": len(kept_ra),
        "kept_overlap_art": len(shared_a),
        "acc_max_diff_on_shared_art": round(float(acc_max_diff_a), 4),
    }
    emit("BENCH_characterize", batched * 1e6,
         f"speedup={payload['speedup_vs_seed_path']}x "
         f"speedup_art={payload['speedup_with_artifact']}x "
         f"proxy_err={payload['proxy_median_rel_err']}", payload)
    with open(ROOT_OUT, "w") as fh:
        json.dump(payload, fh, indent=1)
    ensure_dir()
    print(f"batched {batched:.2f}s (cold {cold:.2f}s) vs reference "
          f"{reference:.2f}s -> {reference / batched:.1f}x; with knob4 "
          f"{batched_art:.2f}s vs {reference_art:.2f}s -> "
          f"{reference_art / batched_art:.1f}x; "
          f"artifacts: {ROOT_OUT} + {RESULTS_DIR}/BENCH_characterize.json")


if __name__ == "__main__":
    main()
