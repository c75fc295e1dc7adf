"""Forced 8-device mesh parity (satellite 3's second half).

``XLA_FLAGS=--xla_force_host_platform_device_count=8`` must be set before
jax initializes, so the sharded half of the parity matrix runs in a child
interpreter: the child builds a 13-camera fleet on an 8-device ``cams``
mesh (lanes padded 13 -> 16), drives it through subset table swaps and
retargets against shadow host controllers, then replays the SceneShift +
InterferenceSpike scenario fused-vs-unfused -- asserting bit-identical
traces and a single placement-stable compiled dispatch throughout.
"""

import os
import pathlib
import subprocess
import sys

ROOT = pathlib.Path(__file__).resolve().parent.parent

CHILD = r"""
import numpy as np

import jax

assert jax.device_count() == 8, jax.devices()

from benchmarks.common import synthetic_controller_table as synthetic_table
from repro.core.characterization import LatencyRegression
from repro.core.controller import (ControllerConfig, FleetController,
                                   LatencyController)
from repro.core.scenario import (CameraSpec, InterferenceSpike, SceneShift,
                                 ScenarioSpec, run_scenario)
from repro.sharding.partition import fleet_mesh, padded_lane_count

# -- manual parity: 13 cams on 8 devices (padded to 16 lanes) ---------------
mesh = fleet_mesh(8)
assert padded_lane_count(13, mesh) == 16

rng = np.random.default_rng(0)
reg = LatencyRegression(slope=1.2e-6, intercept=0.008)
cams, hosts = [], []


class _Cam:
    def __init__(self, cid, ctrl):
        self.camera_id, self.controller = cid, ctrl
        self.table_version = self.qos_version = 0


for i in range(13):
    tbl = synthetic_table(12 + i % 29, smin=2e3 + 37.0 * i,
                          smax=9e4 - 101.0 * i)
    cfg = ControllerConfig(latency_target=0.040 + 0.001 * (i % 17),
                           accuracy_target=0.90 + 0.002 * (i % 4))
    cams.append(_Cam(f"cam{i:03d}", LatencyController(cfg, tbl, reg)))
    hosts.append(LatencyController(cfg, tbl, reg))

fleet = FleetController(cams, capacity=128, mesh=mesh)
assert fleet._n_padded == 16

for step in range(36):
    if step == 10:
        for i in (2, 7, 12):
            fresh = synthetic_table(20 + i, smin=3e3 + 11.0 * i, smax=7e4)
            cams[i].controller.swap_table(fresh)
            cams[i].table_version += 1
            hosts[i].swap_table(fresh)
    if step == 22:
        for i in (0, 5):
            cams[i].controller.set_target(0.075, 0.91)
            cams[i].qos_version += 1
            hosts[i].set_target(0.075, 0.91)
    fb = {c.camera_id: float(rng.uniform(0.005, 0.5)) for c in cams}
    decisions = fleet.decide(fb)
    for i, cam in enumerate(cams):
        dh = hosts[i].update(fb[cam.camera_id])
        df = decisions[cam.camera_id]
        assert df.setting_index == dh.setting_index, (step, i)
        assert df.acted == dh.acted, (step, i)
        assert df.feasible == dh.feasible, (step, i)
assert fleet.cache_size() == 1, fleet.cache_size()

# -- scenario parity: fused 8-device replay == host trace -------------------


def spec(**kw):
    base = dict(
        name="fleet-sharded-parity",
        cameras=tuple(CameraSpec(f"cam{i}", dynamics="medium")
                      for i in range(3)),
        frames=30, seed=9, workload="jaad",
        latency=0.100, accuracy=0.92,
        events=(InterferenceSpike(start=2.0, end=4.0, factor=7.0),),
    )
    base.update(kw)
    return ScenarioSpec(**base)


tables = {"medium": synthetic_table()}
meshed = run_scenario(spec(fleet=True, mesh=mesh), tables=tables)
host = run_scenario(spec(fleet=False), tables=tables)
assert meshed.to_json() == host.to_json()
assert meshed.fleet_cache_size == 1, meshed.fleet_cache_size

print("PARITY_OK")
"""


def test_eight_device_mesh_parity_in_subprocess():
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"          # a virtual CPU mesh, never a chip
    env["XLA_FLAGS"] = ("--xla_force_host_platform_device_count=8 "
                        + env.get("XLA_FLAGS", ""))
    env["PYTHONPATH"] = os.pathsep.join(
        [str(ROOT / "src"), str(ROOT)]
        + ([env["PYTHONPATH"]] if env.get("PYTHONPATH") else []))
    proc = subprocess.run([sys.executable, "-c", CHILD], cwd=ROOT, env=env,
                          capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-4000:]
    assert "PARITY_OK" in proc.stdout
