"""Fleet-scaling benchmark: per-step dispatch cost of the vmapped fleet
controller step at 64 / 128 / 256 cameras -> ``BENCH_fleet.json``.

The claim under test: driving N per-camera PI controllers as ONE compiled
``fleet_controller_step`` makes per-step cost ~FLAT in camera count (the
Python/dispatch overhead is paid once, not N times), where the pre-fleet
path -- one jitted ``controller_step`` call per camera -- scales linearly.
Measured numbers:

  * ``us_per_step``            compiled fleet step, per camera count
  * ``scaling_256_over_64``    flatness: ratio of step cost at 4x the fleet
  * ``python_loop_us_per_step_64``   64 per-camera jitted dispatches
  * ``speedup_vs_python_loop_64``    fleet step vs that loop
  * ``decide_us_per_step_64``  the full broker-facing ``FleetController.
                               decide`` tick (sync + dispatch + readback +
                               host decision objects)
  * ``whole_poll_us``          a REAL ``EdgeBroker.poll_subscription`` --
                               frame fetch + merge + the fused fleet tick --
                               per poll, per camera count
  * ``sharded``                the same whole-poll measurement with the
                               fused tick partitioned over an 8-device mesh
                               (``--xla_force_host_platform_device_count``)
                               at 64 / 512 / 1024 / 4096 lanes, plus the
                               per-camera flatness ratio 4096-vs-64
  * ``multi_tenant``           per-tenant whole-poll cost with 1 / 8 / 64
                               tenant sessions sharing ONE 256-camera fleet
                               (round-robin polls), plus the shared
                               degraded-frame cache hit rate: N tenants at
                               one operating point must pay ~one transform
                               + deflate, so per-tenant cost at 64 tenants
                               stays within 1.5x the single-tenant figure
  * ``cache_size``             compiled variants across the whole sweep of
                               one fleet (must stay 1 per fleet instance)

CI gates these via ``benchmarks/check_regression.py`` against the
conservative thresholds committed in ``benchmarks/baseline_fleet.json``.

  PYTHONPATH=src python -m benchmarks.fleet_sweep [--repeats 5]
      [--skip-sharded]
"""

from __future__ import annotations

import argparse
import json
import math
import os
import subprocess
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.common import (RESULTS_DIR, emit, ensure_dir,
                               synthetic_controller_table)
from repro.compile_cache import enable_compile_cache
from repro.core.characterization import LatencyRegression
from repro.core.controller import (ControllerConfig, ControllerParams,
                                   FleetController, JaxControllerTables,
                                   LatencyController, _controller_step_core,
                                   controller_init, fleet_controller_init,
                                   fleet_controller_step, stack_params,
                                   stack_tables)
ROOT_OUT = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "BENCH_fleet.json")

CAPACITY = 512          # broker TABLE_CAPACITY: the deployed padding
FLEET_SIZES = (64, 128, 256)
STEPS = 200
POLL_SIZES = (64, 256)              # host (1-device) whole-poll sizes
SHARDED_SIZES = (64, 512, 1024, 4096)
SHARDED_DEVICES = 8
POLLS = 25              # timed polls per whole-poll repeat
MAX_FRAMES = 16         # poll_subscription budget (broker default)

synthetic_table = synthetic_controller_table


def build_fleet_arrays(n: int):
    """Stacked tables/params/state for n cameras with varied live rows."""
    reg = LatencyRegression(slope=1.2e-6, intercept=0.008)
    rows, params = [], []
    for i in range(n):
        tbl = synthetic_table(12 + i % 29, smin=2e3 + 37.0 * i,
                              smax=9e4 - 101.0 * i)
        rows.append(JaxControllerTables.from_table(tbl, capacity=CAPACITY))
        params.append(ControllerParams.from_scalars(
            latency_target=0.040 + 0.001 * (i % 17),
            accuracy_target=0.90 + 0.002 * (i % 4),
            slope=reg.slope, intercept=reg.intercept))
    tables = stack_tables(rows)
    return tables, stack_params(params), fleet_controller_init(tables)


BURST = 25          # steps per timed burst


def time_fleet_steps(sizes, *, steps: int, repeats: int) -> dict[int, float]:
    """Per-step wall time of the compiled fleet step for every fleet size.

    Noise-robust on shared runners: many SHORT bursts (min over bursts --
    a deschedule spike poisons one burst, not a whole measurement) with the
    fleet sizes INTERLEAVED, so a noisy period degrades every size equally
    instead of landing on whichever size happened to run then.
    """
    fleets = {}
    for n in sizes:
        tables, params, state = build_fleet_arrays(n)
        step = jax.jit(lambda st, lat, tb, pr: fleet_controller_step(
            st, lat, tb, pr))
        rng = np.random.default_rng(n)
        lat_series = [jnp.asarray(
            rng.uniform(0.005, 0.5, n).astype(np.float32))
            for _ in range(8)]
        state, _ = step(state, lat_series[0], tables, params)   # compile
        jax.block_until_ready(state.integral)
        fleets[n] = [step, state, tables, params, lat_series]
    bursts = max(1, (steps * repeats) // BURST)
    best = {n: float("inf") for n in sizes}
    for b in range(bursts):
        for n in sizes:
            step, s, tables, params, lat_series = fleets[n]
            t0 = time.perf_counter()
            for k in range(BURST):
                s, _ = step(s, lat_series[k % len(lat_series)], tables,
                            params)
            jax.block_until_ready(s.integral)
            best[n] = min(best[n], (time.perf_counter() - t0) / BURST)
            fleets[n][1] = s
    for n in sizes:
        assert fleets[n][0]._cache_size() == 1
    return {n: best[n] * 1e6 for n in sizes}


def time_python_loop(n: int, *, steps: int, repeats: int) -> float:
    """The pre-fleet path: one jitted controller_step dispatch per camera."""
    reg = LatencyRegression(slope=1.2e-6, intercept=0.008)
    cams = []
    step = jax.jit(lambda st, lat, tb, pr: _controller_step_core(
        st, lat, tb, pr))
    for i in range(n):
        tbl = synthetic_table(12 + i % 29, smin=2e3 + 37.0 * i,
                              smax=9e4 - 101.0 * i)
        jt = JaxControllerTables.from_table(tbl, capacity=CAPACITY)
        pr = ControllerParams.from_scalars(
            latency_target=0.040 + 0.001 * (i % 17),
            accuracy_target=0.90 + 0.002 * (i % 4),
            slope=reg.slope, intercept=reg.intercept)
        cams.append((controller_init(jt), jt, pr))
    rng = np.random.default_rng(n)
    lats = rng.uniform(0.005, 0.5, size=(8, n)).astype(np.float32)
    # compile once (shared shapes across cameras)
    st0, aux = step(cams[0][0], jnp.float32(0.1), cams[0][1], cams[0][2])
    jax.block_until_ready(st0.integral)
    best = float("inf")
    for _ in range(repeats):
        states = [c[0] for c in cams]
        t0 = time.perf_counter()
        for k in range(steps):
            row = lats[k % len(lats)]
            for i, (_, jt, pr) in enumerate(cams):
                states[i], aux = step(states[i], row[i], jt, pr)
        jax.block_until_ready(states[-1].integral)
        best = min(best, (time.perf_counter() - t0) / steps)
    return best * 1e6


def time_decide(n: int, *, steps: int, repeats: int) -> float:
    """End-to-end broker tick: FleetController.decide (sync + compiled
    dispatch + device readback + ControlDecision construction)."""
    reg = LatencyRegression(slope=1.2e-6, intercept=0.008)

    class _Cam:
        def __init__(self, i):
            self.camera_id = f"cam{i:03d}"
            tbl = synthetic_table(12 + i % 29, smin=2e3 + 37.0 * i,
                                  smax=9e4 - 101.0 * i)
            self.controller = LatencyController(
                ControllerConfig(0.040 + 0.001 * (i % 17),
                                 0.90 + 0.002 * (i % 4)), tbl, reg)
            self.table_version = 0
            self.qos_version = 0

    cams = [_Cam(i) for i in range(n)]
    fleet = FleetController(cams, capacity=CAPACITY)
    rng = np.random.default_rng(n)
    fbs = [{c.camera_id: float(x) for c, x in
            zip(cams, rng.uniform(0.005, 0.5, n))} for _ in range(4)]
    fleet.decide(fbs[0])                     # compile
    best = float("inf")
    for _ in range(repeats):
        t0 = time.perf_counter()
        for k in range(steps):
            fleet.decide(fbs[k % len(fbs)])
        best = min(best, (time.perf_counter() - t0) / steps)
    assert fleet.cache_size() == 1
    return best * 1e6


def time_whole_poll(n: int, *, polls: int, repeats: int,
                    mesh=None) -> float:
    """Wall time of a REAL ``EdgeBroker.poll_subscription`` over an
    n-camera fleet subscription: frame fetch across the simulated channel,
    timestamp merge, and the single fused controller/drift dispatch.

    Tiny 32x32 frames keep the synthetic payload cost from drowning the
    control plane; each camera publishes just enough frames that the
    subscription never drains mid-measurement (a poll budget of
    ``MAX_FRAMES`` visits only ~16 cameras per round-robin rotation).
    """
    from repro.core.api import QosBounds, SubscriptionOptions
    from repro.core.broker import MezSystem
    from repro.core.channel import calibrated_channel
    from repro.core.session import MezClient
    from repro.data.camera import CameraConfig, SyntheticCamera

    reg = LatencyRegression(slope=1.2e-6, intercept=0.008)
    system = MezSystem(calibrated_channel(seed=11))
    total_polls = 3 + polls * repeats            # warmup + timed
    frames_per_cam = math.ceil(total_polls * MAX_FRAMES / n) + 2
    src = SyntheticCamera(CameraConfig(camera_id="clip", height=32,
                                       width=32, seed=5))
    clip = [(ts, f) for ts, f, _ in src.stream(frames_per_cam)]
    ids = []
    for i in range(n):
        cid = f"cam{i:04d}"
        ids.append(cid)
        cam = system.add_camera(cid)
        cam.background = src.background
        tbl = synthetic_table(12 + i % 29, smin=2e3 + 37.0 * (i % 64),
                              smax=9e4 - 101.0 * (i % 64))
        cam.set_target(0.040 + 0.001 * (i % 17), 0.90 + 0.002 * (i % 4),
                       tbl, reg)
        for ts, f in clip:
            cam.publish(ts, f)
    sess = MezClient(system).open_session("bench")
    sub = sess.subscribe(ids, 0.0, 1e9, qos=QosBounds(0.050, 0.90),
                         options=SubscriptionOptions(fleet=True, mesh=mesh))
    for _ in range(3):                           # warmup (compiles the tick)
        sub.poll(max_frames=MAX_FRAMES)
    best = float("inf")
    for _ in range(repeats):
        t0 = time.perf_counter()
        for _ in range(polls):
            sub.poll(max_frames=MAX_FRAMES)
        best = min(best, (time.perf_counter() - t0) / polls)
    fleet = system.edge.subscription_fleet(sub.subscription_id)
    assert fleet is not None and fleet.cache_size() == 1
    sess.close()
    return best * 1e6


TENANT_CAMS = 256
TENANT_COUNTS = (1, 8, 64)
TENANT_POLLS = 5            # timed round-robin rounds per repeat


def time_tenant_serving(n: int, tenants: int, *, polls: int,
                        repeats: int) -> tuple[float, float]:
    """Per-tenant whole-poll cost with ``tenants`` sessions sharing ONE
    n-camera fleet, plus the shared degraded-frame cache hit rate.

    Each tenant session subscribes every camera at the same operating
    point (the common multi-viewer shape) and the host control path is
    polled round-robin, so tenant cursors stay aligned: the first tenant
    of a round pays the knob transform + deflate, the rest must hit the
    ``EdgeBroker``-owned shared cache.  Returns ``(us_per_tenant_poll,
    cache_hit_rate)``.
    """
    from repro.core.api import QosBounds
    from repro.core.broker import MezSystem
    from repro.core.channel import calibrated_channel
    from repro.core.session import MezClient
    from repro.data.camera import CameraConfig, SyntheticCamera

    reg = LatencyRegression(slope=1.2e-6, intercept=0.008)
    system = MezSystem(calibrated_channel(seed=11))
    rounds = 1 + polls * repeats                 # warmup + timed
    frames_per_cam = math.ceil(rounds * MAX_FRAMES / n) + 2
    src = SyntheticCamera(CameraConfig(camera_id="clip", height=32,
                                       width=32, seed=5))
    clip = [(ts, f) for ts, f, _ in src.stream(frames_per_cam)]
    ids = []
    for i in range(n):
        cid = f"cam{i:04d}"
        ids.append(cid)
        cam = system.add_camera(cid)
        cam.background = src.background
        tbl = synthetic_table(12 + i % 29, smin=2e3 + 37.0 * (i % 64),
                              smax=9e4 - 101.0 * (i % 64))
        cam.set_target(0.040 + 0.001 * (i % 17), 0.90 + 0.002 * (i % 4),
                       tbl, reg)
        for ts, f in clip:
            cam.publish(ts, f)
    client = MezClient(system)
    sessions = []
    for t in range(tenants):
        sess = client.open_session(f"bench-t{t:02d}", tenant=f"t{t:02d}")
        sub = sess.subscribe(ids, 0.0, 1e9, qos=QosBounds(0.050, 0.90))
        sessions.append((sess, sub))
    for _, sub in sessions:                      # warmup round
        sub.poll(max_frames=MAX_FRAMES)
    best = float("inf")
    for _ in range(repeats):
        t0 = time.perf_counter()
        for _ in range(polls):
            for _, sub in sessions:
                sub.poll(max_frames=MAX_FRAMES)
        best = min(best, (time.perf_counter() - t0) / (polls * tenants))
    hit_rate = system.edge.frame_cache.hit_rate()
    for sess, _ in sessions:
        sess.close()
    return best * 1e6, hit_rate


CHILD_MARKER = "WHOLE_POLL_RESULT "


def run_sharded_child(n: int, *, devices: int, polls: int,
                      repeats: int) -> float:
    """Measure ``time_whole_poll`` on a forced ``devices``-device host mesh
    in a SUBPROCESS: ``--xla_force_host_platform_device_count`` only takes
    effect before jax initializes, which this (parent) process already did.
    The child is pinned to the CPU: the mesh it measures is virtual CPU
    devices, and a parent that holds an accelerator keeps it from any
    child."""
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = (f"--xla_force_host_platform_device_count={devices} "
                        + env.get("XLA_FLAGS", "")).strip()
    proc = subprocess.run(
        [sys.executable, "-m", "benchmarks.fleet_sweep",
         "--whole-poll-child", str(n), "--mesh-devices", str(devices),
         "--polls", str(polls), "--repeats", str(repeats)],
        env=env, capture_output=True, text=True, check=True)
    for line in proc.stdout.splitlines():
        if line.startswith(CHILD_MARKER):
            return float(json.loads(line[len(CHILD_MARKER):])["whole_poll_us"])
    raise RuntimeError(f"sharded child (n={n}) produced no result marker:\n"
                       f"{proc.stdout}\n{proc.stderr}")


def main() -> None:
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--repeats", type=int, default=5,
                    help="best-of-N timing repeats (CI runners are noisy)")
    ap.add_argument("--steps", type=int, default=STEPS)
    ap.add_argument("--polls", type=int, default=POLLS,
                    help="timed poll_subscription calls per repeat")
    ap.add_argument("--skip-sharded", action="store_true",
                    help="skip the 8-device mesh subprocess sweep")
    ap.add_argument("--skip-tenants", action="store_true",
                    help="skip the 256-camera multi-tenant serving sweep")
    ap.add_argument("--whole-poll-child", type=int, default=None,
                    metavar="N", help="internal: measure one whole-poll "
                    "size on a forced mesh and print the result marker")
    ap.add_argument("--mesh-devices", type=int, default=None)
    args = ap.parse_args()

    if args.whole_poll_child is not None:
        us = time_whole_poll(args.whole_poll_child, polls=args.polls,
                             repeats=max(args.repeats - 2, 2),
                             mesh=args.mesh_devices)
        print(CHILD_MARKER + json.dumps(
            {"n": args.whole_poll_child, "devices": args.mesh_devices,
             "whole_poll_us": us}))
        return

    out: dict = {"fleet_sizes": list(FLEET_SIZES), "capacity": CAPACITY,
                 "steps": args.steps, "us_per_step": {},
                 "us_per_camera": {}}
    measured = time_fleet_steps(FLEET_SIZES, steps=args.steps,
                                repeats=args.repeats)
    for n in FLEET_SIZES:
        us = measured[n]
        out["us_per_step"][str(n)] = us
        out["us_per_camera"][str(n)] = us / n
        print(f"fleet n={n:4d}: {us:9.1f} us/step  ({us / n:6.2f} us/cam)")
    lo, hi = str(FLEET_SIZES[0]), str(FLEET_SIZES[-1])
    out["scaling_256_over_64"] = (out["us_per_step"][hi]
                                  / out["us_per_step"][lo])
    loop_us = time_python_loop(FLEET_SIZES[0], steps=max(args.steps // 4, 25),
                               repeats=max(args.repeats - 2, 2))
    out["python_loop_us_per_step_64"] = loop_us
    out["speedup_vs_python_loop_64"] = loop_us / out["us_per_step"][lo]
    out["decide_us_per_step_64"] = time_decide(
        FLEET_SIZES[0], steps=max(args.steps // 4, 25),
        repeats=max(args.repeats - 2, 2))

    out["whole_poll_us"] = {}
    out["whole_poll_us_per_cam"] = {}
    for n in POLL_SIZES:
        us = time_whole_poll(n, polls=args.polls,
                             repeats=max(args.repeats - 2, 2))
        out["whole_poll_us"][str(n)] = us
        out["whole_poll_us_per_cam"][str(n)] = us / n
        print(f"poll  n={n:4d}: {us:9.1f} us/poll  ({us / n:6.2f} us/cam)")
    if not args.skip_sharded:
        sh: dict = {"devices": SHARDED_DEVICES, "whole_poll_us": {},
                    "whole_poll_us_per_cam": {}}
        for n in SHARDED_SIZES:
            us = run_sharded_child(n, devices=SHARDED_DEVICES,
                                   polls=args.polls, repeats=args.repeats)
            sh["whole_poll_us"][str(n)] = us
            sh["whole_poll_us_per_cam"][str(n)] = us / n
            print(f"poll  n={n:4d} mesh={SHARDED_DEVICES}: {us:9.1f} "
                  f"us/poll  ({us / n:6.2f} us/cam)")
        lo_n, hi_n = str(SHARDED_SIZES[0]), str(SHARDED_SIZES[-1])
        sh["flatness_4096_over_64"] = (sh["whole_poll_us_per_cam"][hi_n]
                                       / sh["whole_poll_us_per_cam"][lo_n])
        out["sharded"] = sh
        print(f"per-camera whole-poll flatness {hi_n}/{lo_n} on "
              f"{SHARDED_DEVICES}-device mesh: "
              f"{sh['flatness_4096_over_64']:.3f} (<= 1.5 required)")
    if not args.skip_tenants:
        mt: dict = {"cameras": TENANT_CAMS, "tenant_counts":
                    list(TENANT_COUNTS), "poll_us_per_tenant": {},
                    "cache_hit_rate": {}}
        for t in TENANT_COUNTS:
            us, hit = time_tenant_serving(
                TENANT_CAMS, t, polls=TENANT_POLLS,
                repeats=max(args.repeats - 2, 2))
            mt["poll_us_per_tenant"][str(t)] = us
            mt["cache_hit_rate"][str(t)] = hit
            print(f"tenants={t:3d} over n={TENANT_CAMS}: {us:9.1f} us per "
                  f"tenant-poll  (shared-cache hit rate {hit:.3f})")
        lo_t, hi_t = str(TENANT_COUNTS[0]), str(TENANT_COUNTS[-1])
        mt["tenant_poll_ratio_64_over_1"] = (
            mt["poll_us_per_tenant"][hi_t] / mt["poll_us_per_tenant"][lo_t])
        out["multi_tenant"] = mt
        print(f"per-tenant poll ratio {hi_t}/{lo_t} tenants: "
              f"{mt['tenant_poll_ratio_64_over_1']:.3f} (<= 1.5 required)")
    out["cache_size"] = 1                   # asserted inside the timers

    ensure_dir()
    emit("BENCH_fleet", out["us_per_step"][lo],
         f"scaling={out['scaling_256_over_64']:.2f};"
         f"speedup={out['speedup_vs_python_loop_64']:.1f}x", out)
    with open(ROOT_OUT, "w") as fh:
        json.dump(out, fh, indent=1)
        fh.write("\n")
    print(f"python-loop n=64: {loop_us:9.1f} us/step -> "
          f"{out['speedup_vs_python_loop_64']:.1f}x speedup; "
          f"decide n=64: {out['decide_us_per_step_64']:.1f} us/step")
    print(f"artifacts: {ROOT_OUT} + {RESULTS_DIR}/BENCH_fleet.json")


if __name__ == "__main__":
    main()
