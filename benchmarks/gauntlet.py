"""Broker gauntlet: seeded, sustained heavy-traffic phases over one fleet.

The edge-broker benchmarking literature's lesson is that latency claims only
hold up under systematic stress -- throughput, p99.9 tail latency, and
behavior under connection churn are where bugs hide.  This harness drives
scores of concurrent tenant sessions over a shared fleet through sustained
load phases composed from the scenario DSL, and reports per phase:

  * delivered-latency p50 / p95 / p99.9 (milliseconds, pooled over the
    main subscription's trace AND every tenant's delivered frames),
  * the edge's credit ledger (``EdgeBroker.credit_report``): granted /
    returned / in-flight / dropped / leaked fetch credits -- the crash-wave
    phase must end with everything returned,
  * shared-frame-cache hit rate (the 64-tenant churn phase gates on it:
    LRU eviction must keep the hot working set resident through
    subscribe/unsubscribe floods),
  * admission/degradation event tallies (TENANT_DEGRADED,
    ADMISSION_REJECTED, RPC_TIMEOUT, EVENTS_DROPPED, ...).

Phases (each an independent seeded ``ScenarioSpec`` -- one fresh fleet per
phase, so a phase's damage can't leak into the next):

  churn64     64 tenant sessions join in waves and half of them churn
              (leave / rejoin) while the fleet keeps serving.
  qos_storm   a renegotiation storm: the main subscription's QoS bounds
              flip every few hundred milliseconds while tenants hold SLOs.
  crash_wave  camera crash -> recover cycles sweep the fleet (plus an edge
              crash in --full mode); the credit ledger must conserve.
  oversub     the wire budget is capped below aggregate demand while
              tenants of every SLO class pile on: admission control must
              degrade lower classes and reject the infeasible join.
  federated   a two-broker herd serves the fleet through live camera
              migrations, a broker-overload shed, and a rolling edge
              upgrade; the credit ledger is summed herd-wide and the
              migration blackout must stay inside the p99.9 ceiling.

Tables are the shared deterministic synthetic controller tables (no
characterization sweep, no detector, no disk cache), and every random
draw -- channel jitter, synthetic frames -- is seeded, so the emitted
``BENCH_gauntlet.json`` is bit-reproducible for a fixed ``--seed``:
``benchmarks/check_regression.py --gauntlet-fresh`` gates it against the
committed ``benchmarks/baseline_gauntlet.json``.

Run:  python -m benchmarks.gauntlet [--full] [--seed 7] [--phases a,b]
"""

from __future__ import annotations

import argparse
import json
import os
from collections import Counter

import numpy as np

from benchmarks.common import Timer, emit, synthetic_controller_table
from repro.compile_cache import enable_compile_cache
from repro.core.channel import calibrated_channel
from repro.core.characterization import fit_latency_regression
from repro.core.scenario import (BrokerOverload, CameraCrash, CameraMigrate,
                                 CameraRecover, CameraSpec, EdgeCrash,
                                 EdgeRecover, QosChange, RollingUpgrade,
                                 ScenarioSpec, TenantJoin, TenantLeave,
                                 run_scenario)

ROOT_OUT = os.path.join(os.path.dirname(__file__), "..",
                        "BENCH_gauntlet.json")

N_CAMS = 4
FPS = 5.0
LATENCY = 0.100
ACCURACY = 0.92
WORKLOAD = "jaad"
SLO_CYCLE = ("best_effort", "silver", "gold")


def _cameras() -> tuple:
    return tuple(CameraSpec(f"cam{i}", dynamics="medium", fps=FPS)
                 for i in range(N_CAMS))


def _tables() -> dict:
    return {"medium": synthetic_controller_table()}


def _fleet_demand_bps(seed: int, latency: float = LATENCY) -> float:
    """The fleet's aggregate nominal wire demand at ``latency`` bounds,
    mirroring ``EdgeBroker._lane_load`` (nominal operating size from the
    inverted latency regression, workload-scaled, times fps) -- used to
    size the oversubscription phase's wire budget deterministically."""
    tbl = synthetic_controller_table()
    ch = calibrated_channel(seed=seed, workload=WORKLOAD)
    sizes = np.linspace(tbl.sizes_sorted[0], tbl.sizes_sorted[-1], 16)
    reg = fit_latency_regression(sizes,
                                 ch.regression_points(sizes, n=N_CAMS))
    nominal = float(np.clip(reg.invert(latency), tbl.sizes_sorted[0],
                            tbl.sizes_sorted[-1]))
    return ch.scaled_bytes(nominal) * FPS * N_CAMS


# =============================================================================
# Phase builders: (name, full) -> ScenarioSpec
# =============================================================================


def phase_churn64(seed: int, *, full: bool = False,
                  tenants: int = 64) -> ScenarioSpec:
    """Subscribe/unsubscribe churn flood: ``tenants`` sessions join in
    waves; every odd tenant leaves mid-run and every fourth rejoins --
    sustained connection churn while the shared cache serves the stable
    majority."""
    frames = 80 if full else 40
    t_end = frames / FPS
    events = []
    for i in range(tenants):
        at = round(0.2 + (i % 16) * 0.04 + (i // 16) * 0.25 * t_end, 3)
        events.append(TenantJoin(at=at, tenant=f"t{i:03d}",
                                 slo=SLO_CYCLE[i % 3]))
        if i % 2 == 1:
            events.append(TenantLeave(at=round(at + 0.25 * t_end, 3),
                                      tenant=f"t{i:03d}"))
        if i % 4 == 1:
            events.append(TenantJoin(at=round(at + 0.5 * t_end, 3),
                                     tenant=f"t{i:03d}",
                                     slo=SLO_CYCLE[i % 3]))
    return ScenarioSpec(
        name="gauntlet-churn64", cameras=_cameras(), frames=frames,
        seed=seed, workload=WORKLOAD, latency=LATENCY, accuracy=ACCURACY,
        events=tuple(sorted(events, key=lambda e: e.at)))


def phase_qos_storm(seed: int, *, full: bool = False) -> ScenarioSpec:
    """QoS-renegotiation storm: the main subscription's bounds flip every
    0.4 s of stream time while 8 SLO-classed tenants hold subscriptions
    (every renegotiation re-divides the wire budget across them)."""
    frames = 80 if full else 40
    t_end = frames / FPS
    events = [TenantJoin(at=round(0.2 + 0.1 * i, 3), tenant=f"q{i}",
                         slo=SLO_CYCLE[i % 3]) for i in range(8)]
    lo, hi = 0.060, 0.160
    t, flip = 1.0, 0
    while t < t_end - 0.5:
        events.append(QosChange(at=round(t, 3),
                                latency=(lo if flip % 2 == 0 else hi),
                                accuracy=(0.90 if flip % 4 < 2 else 0.94)))
        t += 0.4
        flip += 1
    return ScenarioSpec(
        name="gauntlet-qos-storm", cameras=_cameras(), frames=frames,
        seed=seed + 1, workload=WORKLOAD, latency=LATENCY,
        accuracy=ACCURACY, events=tuple(sorted(events, key=lambda e: e.at)))


def phase_crash_wave(seed: int, *, full: bool = False) -> ScenarioSpec:
    """Camera crash -> recover cycles sweep the fleet round-robin while 8
    tenants stream (every crash strands the credits of in-flight fetches;
    every recover must hand them back).  ``--full`` adds an edge-broker
    crash/recover cycle on top."""
    frames = 120 if full else 60
    t_end = frames / FPS
    events = [TenantJoin(at=round(0.2 + 0.1 * i, 3), tenant=f"c{i}",
                         slo=SLO_CYCLE[i % 3]) for i in range(8)]
    t, wave = 1.0, 0
    while t + 1.0 < t_end - 1.0:
        cam = f"cam{wave % N_CAMS}"
        events.append(CameraCrash(at=round(t, 3), camera_id=cam))
        events.append(CameraRecover(at=round(t + 1.0, 3), camera_id=cam))
        t += 1.5
        wave += 1
    if full:
        events.append(EdgeCrash(at=round(t_end * 0.55, 3)))
        events.append(EdgeRecover(at=round(t_end * 0.60, 3)))
    return ScenarioSpec(
        name="gauntlet-crash-wave", cameras=_cameras(), frames=frames,
        seed=seed + 2, workload=WORKLOAD, latency=LATENCY,
        accuracy=ACCURACY, events=tuple(sorted(events, key=lambda e: e.at)))


def phase_oversub(seed: int, *, full: bool = False) -> ScenarioSpec:
    """Oversubscription soak: the wire budget is pinned to the untenanted
    main stream's demand plus ~1.2 gold-tenant demands while 12 tenants of
    every class pile on -- lower classes must degrade toward their floors
    -- and one reject-policy join demanding near-perfect accuracy (its
    floor alone busts the budget) must bounce."""
    frames = 120 if full else 60
    demand = _fleet_demand_bps(seed + 3)
    events = [TenantJoin(at=round(0.3 + 0.2 * i, 3), tenant=f"o{i:02d}",
                         slo=SLO_CYCLE[i % 3]) for i in range(12)]
    events.append(TenantJoin(at=2.9, tenant="greedy", slo="gold",
                             accuracy=0.999, admission="reject"))
    events.append(TenantLeave(at=round(frames / FPS * 0.7, 3),
                              tenant="o00"))
    return ScenarioSpec(
        name="gauntlet-oversub", cameras=_cameras(), frames=frames,
        seed=seed + 3, workload=WORKLOAD, latency=LATENCY,
        accuracy=ACCURACY, wire_budget=demand * 2.2,
        events=tuple(sorted(events, key=lambda e: e.at)))


def phase_federated(seed: int, *, full: bool = False) -> ScenarioSpec:
    """Federated herd under churn: two brokers split the fleet while 8
    SLO-classed tenants stream; live ``CameraMigrate``s move cameras
    between brokers mid-poll (the migration blackout must stay inside the
    p99.9 ceiling -- no frame loss, no duplicate), a ``BrokerOverload``
    halves one broker's backhaul so the overload policy sheds the newest
    best-effort lanes, and a ``RollingUpgrade`` restarts every broker in
    turn with zero subscriber-visible downtime.  The credit ledger is
    summed HERD-wide, so conservation here proves the migration drain /
    re-grant handshake leaks nothing."""
    frames = 120 if full else 60
    t_end = frames / FPS
    events: list = [TenantJoin(at=round(0.2 + 0.1 * i, 3), tenant=f"f{i}",
                               slo=SLO_CYCLE[i % 3]) for i in range(8)]
    # live migrations against the default round-robin placement
    # (cam0,cam2 -> broker 0; cam1,cam3 -> broker 1)
    events.append(CameraMigrate(at=round(t_end * 0.25, 3),
                                camera_id="cam0", to_broker=1))
    events.append(CameraMigrate(at=round(t_end * 0.35, 3),
                                camera_id="cam3", to_broker=0))
    # degraded backhaul on broker 0: the overload policy must fire
    # BROKER_OVERLOAD and shed newest best-effort lanes to broker 1
    events.append(BrokerOverload(at=round(t_end * 0.5, 3), broker=0,
                                 factor=0.5))
    # rolling edge upgrade: migrate-then-restart each broker in turn
    events.append(RollingUpgrade(at=round(t_end * 0.7, 3)))
    return ScenarioSpec(
        name="gauntlet-federated", cameras=_cameras(), frames=frames,
        seed=seed + 4, workload=WORKLOAD, latency=LATENCY,
        accuracy=ACCURACY, n_brokers=2,
        events=tuple(sorted(events, key=lambda e: e.at)))


PHASES = {
    "churn64": phase_churn64,
    "qos_storm": phase_qos_storm,
    "crash_wave": phase_crash_wave,
    "oversub": phase_oversub,
    "federated": phase_federated,
}


# =============================================================================
# Phase runner + metric extraction
# =============================================================================


def _pct(lats_ms: np.ndarray, q: float) -> float:
    return float(np.percentile(lats_ms, q)) if lats_ms.size else float("nan")


def run_phase(name: str, spec: ScenarioSpec) -> dict:
    with Timer() as t:
        res = run_scenario(spec, tables=_tables())
    lats = [r.latency_s for r in res.rows if r.latency_s is not None]
    dropped = sum(1 for r in res.rows if r.dropped)
    for s in (res.tenant_stats or {}).values():
        dropped += s["dropped"]
    for samples in (res.tenant_latencies or {}).values():
        lats.extend(samples)
    lats_ms = np.asarray(lats, np.float64) * 1e3
    ev = Counter(e["kind"] for e in res.events_log)
    tenants = res.tenant_stats or {}
    return {
        "phase": name,
        "scenario": spec.name,
        "seed": spec.seed,
        "sessions": 1 + sum(1 for e in spec.events
                            if isinstance(e, TenantJoin)),
        "tenants_admitted": sum(1 for s in tenants.values()
                                if s["admitted"]),
        "frames_delivered": int(len(lats)),
        "frames_dropped": int(dropped),
        "p50_ms": _pct(lats_ms, 50),
        "p95_ms": _pct(lats_ms, 95),
        "p999_ms": _pct(lats_ms, 99.9),
        "credits": res.credit_stats,
        "cache": res.cache_stats,
        "events": {k: int(v) for k, v in sorted(ev.items())},
        "tenant_degraded": int(ev.get("tenant_degraded", 0)),
        "admission_rejected": int(ev.get("admission_rejected", 0)),
        "camera_migrated": int(ev.get("camera_migrated", 0)),
        "broker_overload": int(ev.get("broker_overload", 0)),
        "rpc_timeouts": int(ev.get("rpc_timeout", 0)),
        "wall_s": round(t.seconds, 3),
    }


def run_gauntlet(*, seed: int = 7, full: bool = False,
                 phases: list[str] | None = None) -> dict:
    names = phases if phases else list(PHASES)
    out: dict = {"bench": "gauntlet", "mode": "full" if full else "quick",
                 "seed": seed, "phases": {}}
    for name in names:
        spec = PHASES[name](seed, full=full)
        m = run_phase(name, spec)
        out["phases"][name] = m
        print(f"  {name:12s} sessions={m['sessions']:3d} "
              f"delivered={m['frames_delivered']:5d} "
              f"p50={m['p50_ms']:.1f}ms p95={m['p95_ms']:.1f}ms "
              f"p99.9={m['p999_ms']:.1f}ms "
              f"cache={m['cache']['hit_rate']:.3f} "
              f"credits(leaked={m['credits']['leaked']} "
              f"in_flight={m['credits']['in_flight']} "
              f"dropped={m['credits']['dropped']}) "
              f"degraded={m['tenant_degraded']} "
              f"rejected={m['admission_rejected']} [{m['wall_s']:.1f}s]")
    return out


def main() -> None:
    enable_compile_cache()
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--seed", type=int, default=7)
    ap.add_argument("--full", action="store_true",
                    help="long soak phases (slow; CI runs these in the "
                         "race-guarded slow job)")
    ap.add_argument("--phases", type=str, default=None,
                    help=f"comma-separated subset of {sorted(PHASES)}")
    ap.add_argument("--out", type=str, default=ROOT_OUT)
    args = ap.parse_args()
    phases = args.phases.split(",") if args.phases else None
    if phases:
        unknown = [p for p in phases if p not in PHASES]
        if unknown:
            ap.error(f"unknown phases {unknown}; pick from {sorted(PHASES)}")
    payload = run_gauntlet(seed=args.seed, full=args.full, phases=phases)
    total_us = sum(m["wall_s"] for m in payload["phases"].values()) * 1e6
    emit("gauntlet", total_us, "phases={}".format(len(payload["phases"])),
         payload)
    with open(args.out, "w") as fh:
        json.dump(payload, fh, indent=1, sort_keys=True)
    print("wrote", os.path.normpath(args.out))


if __name__ == "__main__":
    main()
