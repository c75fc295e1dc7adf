"""Smoke test of Mez's device path on one TPU chip.

Drives the main path once, in one process, at the ``configs/mez_edge``
deployment size (5 cameras at 5 fps, 144x256 frames, a 32-frame
characterization clip, a 100 ms / 0.95 target):

  device        the platform, kind and count JAX reports; anything but a
                TPU exits non-zero before any work,
  characterize  the full 1350-setting knob grid (knob4 included) through
                ``characterize(engine="batched")``: the Pallas
                ``frame_knob_grid`` kernel, compiled by Mosaic, and the
                device labeler -- plus one (resolution, colorspace) group
                re-run and compared bit for bit with
                ``kernels.ref.frame_knob_grid_ref`` on the CPU device;
                it prints the labeler's scan rounds of each group,
  session       a 5-camera ``MezClient`` session subscribed with
                ``SubscriptionOptions(fleet=True)``, so the fused fleet
                tick runs on the chip, drained and compared frame for
                frame with the host PI path (``fleet=False``).

With ``--four-chips`` it runs only the mesh-sharded fleet tick: a
``fleet_mesh(4)`` tick over 4096 lanes against the same tick on one
device, decisions byte-identical, lanes spread over all four devices.

Each phase prints one line (characterize two); the last line of standard
output is one JSON object, ``{"ok": true, "device": {...}}``.  A failed
phase raises, so the script exits non-zero and prints no such line.

    python chip_smoke.py [--four-chips]
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
for _p in (os.path.join(ROOT, "src"), ROOT):
    if _p not in sys.path:
        sys.path.insert(0, _p)

FOUR_CHIP_LANES = 4096


class SmokeFailure(RuntimeError):
    pass


def check(cond: bool, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


def phase_device(want: int) -> dict:
    import jax

    devs = jax.devices()
    d = devs[0]
    info = {"platform": d.platform, "kind": d.device_kind,
            "count": len(devs)}
    print(f"[device] platform={info['platform']} kind={info['kind']} "
          f"count={info['count']}", flush=True)
    check(d.platform == "tpu", f"no TPU: JAX found {d.platform!r} devices")
    check(len(devs) >= want, f"need {want} chips, found {len(devs)}")
    return info


def phase_characterize():
    """The full grid on the chip; returns the characterization table."""
    import jax
    import numpy as np

    from repro.configs.mez_edge import CONFIG as EDGE
    from repro.core import grid_engine as GE
    from repro.core import knobs as K
    from repro.core.characterization import characterize
    from repro.data.camera import CameraConfig, SyntheticCamera
    from repro.kernels import frame_knobs as FK
    from repro.kernels import ref

    def factory():
        return SyntheticCamera(CameraConfig(
            dynamics="complex", seed=EDGE.seed, height=EDGE.frame_height,
            width=EDGE.frame_width))

    # keep each group's labeler inputs, to count its rounds after the sweep
    label, inputs = GE._label_group, []

    def kept(diff, eff):
        inputs.append((diff, eff))
        return label(diff, eff)

    GE._label_group = kept
    try:
        t0 = time.perf_counter()
        table = characterize(factory, clip_len=EDGE.characterization_clip,
                             include_artifact=True, engine="batched")
        sweep_s = time.perf_counter() - t0
    finally:
        GE._label_group = label
    rounds = [f"{d.shape[2]}x{d.shape[3]}:{GE.label_rounds(d, e)}"
              for d, e in inputs]
    print(f"[labeler] scan rounds of each of the sweep's {len(inputs)} "
          f"groups: {' '.join(rounds)}", flush=True)
    n_art = sum(s.artifact > 0 for s in table.settings)
    grid_size = (len(K.RESOLUTION_SCALES) * len(K.COLORSPACES)
                 * len(K.BLUR_KERNELS) * len(K.ARTIFACT_MODES)
                 * len(K.DIFF_THRESHOLDS))
    check(len(table.settings) > 0, "characterization kept no settings")
    # which branch run_grid took, from the jit caches of this process
    check(FK._grid_call._cache_size() > 0,
          "the Pallas grid kernel never compiled: run_grid took the XLA "
          "twin")
    check(GE._transform_group._cache_size() == 0,
          "the XLA twin ran on the chip")
    check(GE._label_group._cache_size() > 0,
          "the device labeler never compiled: labels came from the host")

    # one group again, kernel on the chip vs the oracle on the CPU device
    res, cs = 1, FK.CS_YUV420
    cam = factory()
    bg = cam.background
    frames = [cam.next_frame()[1]
              for _ in range(EDGE.characterization_clip)]
    fj, prevj, bgj, enj = GE.stage_clip(bg, frames)
    h, w = bg.shape[:2]
    plan = FK.build_transform_plan(
        h, w, scale=K.RESOLUTION_SCALES[res], cs=cs,
        blur_ks=K.BLUR_KERNELS, art_modes=(0, 1, 2))

    def group(f, p, b, e):
        return FK.frame_knob_grid(f, p, plan, background=b, art_enable=e)

    hlo = jax.jit(group).lower(fj, prevj, bgj, enj).as_text()
    check("tpu_custom_call" in hlo,
          "frame_knob_grid did not lower to a Mosaic tpu_custom_call")
    payload, feats, _ = group(fj, prevj, bgj, enj)
    payload = np.asarray(payload)
    cpu = jax.devices("cpu")[0]
    host = [jax.device_put(np.asarray(x), cpu) for x in (fj, prevj, bgj, enj)]
    with jax.default_device(cpu):
        want, want_feats, _ = ref.frame_knob_grid_ref(
            host[0], host[1], plan, background=host[2], art_enable=host[3])
    want = np.asarray(want)
    check(payload.shape == want.shape,
          f"payload shape {payload.shape} != oracle {want.shape}")
    bad = int((payload != want).sum())
    feat_rel = float(np.max(np.abs(np.asarray(feats) - np.asarray(want_feats))
                            / np.maximum(np.abs(np.asarray(want_feats)), 1)))
    print(f"[characterize] table={len(table.settings)} settings of "
          f"{grid_size} swept with knob4 ({n_art} knob4 kept at the "
          f"{table.min_accuracy} floor) in {sweep_s:.1f}s; "
          f"pallas=tpu_custom_call labeler=device; group res={res} "
          f"cs=yuv420 payload {payload.shape} mismatches={bad} "
          f"feats max rel diff {feat_rel:.2e}", flush=True)
    check(bad == 0, f"{bad} payload bytes differ from the CPU oracle")
    return table


def _run_session(table, *, fleet: bool):
    import numpy as np

    from repro.configs.mez_edge import CONFIG as EDGE
    from repro.core.api import QosBounds, SubscriptionOptions
    from repro.core.broker import MezSystem
    from repro.core.channel import calibrated_channel
    from repro.core.characterization import fit_latency_regression
    from repro.core.session import MezClient
    from repro.data.camera import CameraConfig, SyntheticCamera

    channel = calibrated_channel(seed=3, workload="jaad")
    system = MezSystem(channel)
    sizes = np.linspace(table.sizes_sorted[0], table.sizes_sorted[-1], 16)
    regression = fit_latency_regression(
        sizes, channel.regression_points(sizes, n=EDGE.num_cameras))
    frames = 40
    ids = [f"cam{i}" for i in range(EDGE.num_cameras)]
    for cid in ids:
        cam = system.add_camera(cid)
        src = SyntheticCamera(CameraConfig(
            camera_id=cid, dynamics="complex", seed=EDGE.seed,
            height=EDGE.frame_height, width=EDGE.frame_width, fps=EDGE.fps))
        cam.background = src.background
        cam.set_target(EDGE.latency_target, EDGE.accuracy_target, table,
                       regression)
        for ts, frame, _ in src.stream(frames):
            cam.publish(ts, frame)
    # every camera is fetched on every poll (fetch_window frames each):
    # the fused tick steps all lanes per poll, so parity with the host PI
    # path, which steps a camera when it is fetched, needs that
    budget = EDGE.num_cameras * EDGE.fetch_window
    delivered = []
    with MezClient(system).open_session("smoke") as session:
        sub = session.subscribe(
            ids, 0.0, frames / EDGE.fps,
            qos=QosBounds(EDGE.latency_target, EDGE.accuracy_target),
            options=SubscriptionOptions(fleet=fleet))
        while batch := sub.poll(max_frames=budget):
            delivered += [(d.camera_id, d.timestamp, d.knob_index,
                           d.wire_bytes) for d in batch.delivered]
        state = sub.state.value
        fc = system.edge.subscription_fleet(sub.subscription_id)
        cache = fc.cache_size() if fc is not None else None
        platform = (next(iter(fc.state.integral.devices())).platform
                    if fc is not None else None)
        sub.close()
    return delivered, state, cache, platform


def phase_session(table) -> None:
    from repro.configs.mez_edge import CONFIG as EDGE

    t0 = time.perf_counter()
    fleet, state, cache, platform = _run_session(table, fleet=True)
    fleet_s = time.perf_counter() - t0
    host, host_state, _, _ = _run_session(table, fleet=False)
    same = fleet == host
    cams = len({c for c, *_ in fleet})
    knobs = sorted({k for _, _, k, _ in fleet})
    print(f"[session] fleet=True drained={state} delivered={len(fleet)} "
          f"frames from {cams} cameras in {fleet_s:.1f}s, settings "
          f"served {knobs}; fleet tick on "
          f"{platform}, cache_size={cache}; host-PI run delivered="
          f"{len(host)} ({host_state}), per-frame settings equal={same}",
          flush=True)
    check(len(fleet) > 0 and cams == EDGE.num_cameras,
          "the fleet session delivered "
          f"{len(fleet)} frames from {cams} cameras")
    check(state == host_state == "drained", f"subscription ended {state}")
    check(platform == "tpu", f"the fleet tick ran on {platform}")
    check(cache == 1, f"fleet tick compiled {cache} variants, want 1")
    check(same, "fleet-mode frames/settings differ from the host PI run")


def phase_four_chips() -> None:
    """4096-lane fleet tick on a 4-device mesh vs one device."""
    import dataclasses

    import jax
    import numpy as np

    from benchmarks.common import synthetic_controller_table
    from repro.core.characterization import LatencyRegression
    from repro.core.controller import (ControllerConfig, FleetController,
                                       LatencyController)
    from repro.sharding.partition import fleet_mesh

    @dataclasses.dataclass
    class Cam:                 # what FleetController reads off a CamBroker
        camera_id: str
        controller: LatencyController
        table_version: int = 0
        qos_version: int = 0

    n = FOUR_CHIP_LANES
    reg = LatencyRegression(slope=1.2e-6, intercept=0.008)
    tables = [synthetic_controller_table(12 + k, smin=2e3 + 37.0 * k,
                                         smax=9e4 - 101.0 * k)
              for k in range(29)]
    cams = [Cam(f"cam{i:04d}", LatencyController(
        ControllerConfig(0.040 + 0.001 * (i % 17), 0.90 + 0.002 * (i % 4)),
        tables[i % 29], reg)) for i in range(n)]
    meshed = FleetController(cams, capacity=128, mesh=fleet_mesh(4))
    single = FleetController(cams, capacity=128)
    rng = np.random.default_rng(0)
    t0 = time.perf_counter()
    for _ in range(8):
        lat = rng.uniform(0.005, 0.5, n).astype(np.float32)
        valid = rng.random(n) < 0.9
        dm = dict(meshed.tick(lat, valid))
        ds = dict(single.tick(lat, valid))
        check(dm == ds, "meshed decisions differ from the one-device tick")
        for a, b in zip(jax.tree_util.tree_leaves(meshed.state),
                        jax.tree_util.tree_leaves(single.state)):
            check(np.asarray(a).tobytes() == np.asarray(b).tobytes(),
                  "meshed PI state differs from the one-device tick")
    tick_s = time.perf_counter() - t0
    lanes = {}
    for shard in meshed.state.integral.addressable_shards:
        lanes[str(shard.device.id)] = lanes.get(str(shard.device.id), 0) \
            + shard.data.shape[0]
    print(f"[four-chips] fleet_mesh(4) tick over {n} lanes: 8 ticks, "
          f"decisions byte-identical to one device={dm == ds}; lanes per "
          f"device {lanes}; cache_size meshed={meshed.cache_size()} "
          f"single={single.cache_size()}; {tick_s:.1f}s", flush=True)
    check(len(lanes) == 4 and set(lanes.values()) == {n // 4},
          f"lanes not spread over 4 devices: {lanes}")
    check(meshed.cache_size() == 1, "meshed tick recompiled")


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the 4-device mesh-sharded fleet tick")
    args = ap.parse_args()
    if not os.path.isdir(os.path.join(ROOT, "src", "repro")):
        print("chip_smoke.py must run from a checkout of the repo",
              file=sys.stderr)
        return 2

    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()
    device = phase_device(4 if args.four_chips else 1)
    if args.four_chips:
        phase_four_chips()
    else:
        table = phase_characterize()
        phase_session(table)
    print(json.dumps({"ok": True, "device": device}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
