"""Tests of the benchmark harness, on the CPU at small sizes.

They drive whole runs with the harness's look for a chip skipped: a sound
run is correct, and the control and each planted fault are not.

    PYTHONPATH=src python -m pytest -q mezbench/tests
"""

from __future__ import annotations

import functools
import json
import os
import shutil
import subprocess
import sys
import time

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
for _p in (ROOT, os.path.join(ROOT, "src")):
    if _p not in sys.path:
        sys.path.insert(0, _p)

from mezbench import kernel_cost, run, trace_reduce  # noqa: E402
from mezbench.reference import knobs as RK  # noqa: E402

SEED = 4_000_000_017          # seeds above 2**31 have to work


def _small(cell):
    cell.config.update(frame_height=96, frame_width=128,
                       characterization_clip=16)


def _interpret_kernel(monkeypatch):
    """Route characterization through the Pallas kernel in interpret mode,
    the path the chip takes (the CPU otherwise takes the XLA twin)."""
    from repro.core import grid_engine as GE
    from repro.kernels import frame_knobs as FK

    monkeypatch.setattr(FK, "frame_knob_grid", functools.partial(
        FK.frame_knob_grid, interpret=True))
    orig = GE.run_grid

    def run_grid(*a, use_pallas=None, **k):
        return orig(*a, use_pallas=True if use_pallas is None else use_pallas,
                    **k)
    monkeypatch.setattr(GE, "run_grid", run_grid)


def _run(capsys, workload, *extra, patch=_small, seconds=2.0):
    rc = run.main(["--workload", workload, "--seed", str(SEED), "--seconds",
                   str(seconds), "--trace", "0", *extra],
                  require_tpu=False, t_start=time.perf_counter(), patch=patch)
    cap = capsys.readouterr()
    out = cap.out.strip().splitlines()
    assert rc == 0
    res = json.loads(out[-1])
    extra = [ln for ln in cap.err.splitlines() if " extra=" in ln][-1]
    # the harness's own numbers first: the line's last key stays "checks"
    return {"extra": json.loads(extra.split(" extra=", 1)[1]), **res}


@pytest.fixture(scope="module", autouse=True)
def _cpu_only():
    import jax

    if jax.default_backend() != "cpu":
        pytest.skip("these tests drive the CPU path")


def test_no_tpu_exits_nonzero_before_work(capsys):
    rc = run.main(["--workload", "testbed.steady", "--seed", "1",
                   "--seconds", "1", "--trace", "0"])
    assert rc != 0
    assert capsys.readouterr().out.strip() == ""


def test_checkout_without_program_exits_nonzero(tmp_path):
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(BENCH, tmp_path / "mezbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = subprocess.run([sys.executable, "mezbench/run.py", "--workload",
                        "testbed.steady", "--seed", "1", "--seconds", "1",
                        "--trace", "0"], cwd=tmp_path, capture_output=True,
                       text=True, timeout=120,
                       env={k: v for k, v in os.environ.items()
                            if k != "PYTHONPATH"})
    assert p.returncode != 0
    assert p.stdout.strip() == ""


def test_serving_run_is_correct_and_control_is_not(capsys):
    res = _run(capsys, "testbed.steady", "--control", "1")
    checks = res["checks"]
    assert res["correct"] is True
    assert checks["payload_off"]["value"] == 0
    assert checks["settings_off"]["value"] == 0
    assert checks["control.payload_off"]["value"] > 0
    assert list(res)[-1] == "checks"
    assert {"deliver_p95_ms", "setup_s"} <= set(res["metrics"])


def test_serving_fault_altered_payload(capsys, monkeypatch):
    from repro.core import broker

    orig = broker.CamBroker.fetch

    def fetch(self, *a, **k):
        out = orig(self, *a, **k)
        for i, d in enumerate(out):
            if d.frame is not None and d.timestamp >= 1.0:
                bad = d.frame.copy()
                bad.flat[0] ^= 1
                out[i] = broker.dataclasses.replace(d, frame=bad)
        return out
    monkeypatch.setattr(broker.CamBroker, "fetch", fetch)
    res = _run(capsys, "testbed.steady")
    assert res["correct"] is False
    assert res["checks"]["payload_off"]["value"] > 0


def test_serving_fault_controller_state_frozen(capsys, monkeypatch):
    """At a 60 ms target the controller moves between settings; a fused
    tick that never steps its lanes serves stale ones.  (The target costs
    more accuracy than the floor allows, so only the replay's numbers are
    asserted of the sound run.)"""
    def tight(cell):
        cell.config.update(latency_target=0.06)
    res = _run(capsys, "testbed.steady", patch=tight, seconds=4.0)
    for k in ("settings_off", "payload_off", "order_off"):
        assert res["checks"][k]["value"] == 0, res["checks"]

    from repro.core import controller

    orig = controller.FleetController.tick

    def tick(self, lat, valid, *a, **k):
        return orig(self, lat, np.zeros_like(np.asarray(valid, bool)), *a,
                    **k)
    monkeypatch.setattr(controller.FleetController, "tick", tick)
    res = _run(capsys, "testbed.steady", patch=tight, seconds=4.0)
    assert res["correct"] is False
    assert res["checks"]["settings_off"]["value"] > 0


def test_serving_fault_blank_payloads(capsys, monkeypatch):
    """Frames served blank: the payloads are wrong and the subscriber
    finds no pedestrian, so normalized F1 falls below its limit."""
    from repro.core import broker
    from mezbench.tests import faults

    monkeypatch.setattr(broker.CamBroker, "fetch", broker.CamBroker.fetch)
    faults.plant_blank_payloads()
    res = _run(capsys, "testbed.steady")
    assert res["correct"] is False
    assert res["checks"]["payload_off"]["value"] > 0
    assert res["checks"]["f1_norm"]["value"] < res["checks"]["f1_norm"][
        "limit"]


def test_fanout_sampled_tenants_are_correct(capsys):
    def patch(cell):
        _small(cell)
        cell.traffic.update(tenants=[{"count": 4, "cameras": "all"}],
                            check_subscriptions=2)
        cell.config.update(fps=10.0)
    res = _run(capsys, "duke8.fanout64", patch=patch)
    assert res["correct"] is True, res["checks"]
    assert "delivered_fps" in res["metrics"]


def test_fanout_tenants_behind_the_log_resume_at_its_oldest_frame(
        capsys, monkeypatch):
    """A camera log far shorter than the tenants' lag: frames evicted
    before a tenant fetched them are skipped, and the check allows that
    and nothing more."""
    import inspect

    from repro.core import broker

    init = broker.CamBroker.__init__
    params = inspect.signature(init).parameters

    def small_log(self, *a, **k):
        k["log_capacity"] = 24
        init(self, *a, **k)
    assert "log_capacity" in params
    monkeypatch.setattr(broker.CamBroker, "__init__", small_log)

    def patch(cell):
        _small(cell)
        cell.traffic.update(tenants=[{"count": 16, "cameras": "all"}],
                            check_subscriptions=2)
        cell.config.update(fps=60.0, log_capacity=24)
    res = _run(capsys, "duke8.fanout64", patch=patch, seconds=3.0)
    assert res["correct"] is True, res["checks"]
    assert res["extra"]["evicted"] > 0


def test_serving_schedule_and_camera_sets_are_data(capsys):
    """On/off bursts and tenants on camera subsets drawn by weight come
    from the traffic file alone, and the check follows them."""
    def patch(cell):
        _small(cell)
        cell.traffic.update(
            tenants=[{"count": 2, "cameras": [0, 2]},
                     {"count": 2, "cameras": {"draw": 3, "zipf": 1.0}}],
            schedule=[{"seconds": 0.6, "rate": 2.0},
                      {"seconds": 0.4, "rate": 0.0}],
            check_subscriptions=4)
    res = _run(capsys, "testbed.steady", patch=patch, seconds=3.0)
    assert res["correct"] is True, res["checks"]
    assert res["attempted"] > 0


def test_tick_times_follow_the_schedule():
    from mezbench import serve

    assert serve.tick_times(5.0, None, 3) == [0.0, 0.2, 0.4]
    t = serve.tick_times(10.0, [{"seconds": 0.3, "rate": 1.0},
                                {"seconds": 0.5, "rate": 0.0}], 5)
    np.testing.assert_allclose(t, [0.0, 0.1, 0.2, 0.8, 0.9])
    sets = serve.camera_sets([{"count": 3, "cameras": {"draw": 2,
                                                      "zipf": 2.0}}], 8, 5)
    assert len(sets) == 3 and all(len(c) == 2 for c in sets)


def test_scene_seed_gives_every_seed_the_same_scenes():
    from mezbench import serve

    cfg = {"num_cameras": 4, "frame_height": 24, "frame_width": 32,
           "dynamics": "complex"}
    tr = {"scene_seed": 3}
    a, b = (serve.cameras(s, tr, cfg, 2) for s in (1, 2 ** 35 + 1))
    assert [c.camera_id for c in a] == ["cam0", "cam1", "cam2", "cam3"]
    np.testing.assert_array_equal(a[0].frames[0], b[0].frames[0])
    bgs = lambda cams: sorted(c.background.tobytes() for c in cams)
    assert bgs(a) == bgs(b)


def test_onboard_run_is_correct_and_altered_payload_is_not(
        capsys, monkeypatch):
    _interpret_kernel(monkeypatch)
    res = _run(capsys, "testbed.onboard", seconds=0.1)
    assert res["correct"] is True, res["checks"]
    assert "char_s" in res["metrics"]

    from repro.kernels import frame_knobs as FK
    good = FK.frame_knob_grid

    def altered(*a, **k):
        payload, feats, changed = good(*a, **k)
        return payload.at[0, 1, 0, 0, 0].add(1), feats, changed
    monkeypatch.setattr(FK, "frame_knob_grid", altered)
    res = _run(capsys, "testbed.onboard", seconds=0.1)
    assert res["correct"] is False
    assert res["checks"]["payload_bytes_off"]["value"] > 0


def test_onboard_fault_labeler_one_round_short(capsys, monkeypatch):
    """The device labeler one round short of its fixed point moves the
    table's accuracies.  At the cells' frame size, where components are
    wide enough for the last round to matter; the grid takes the XLA
    twin and labels on the device labeler, as the chip does."""
    from repro.core import grid_engine as GE
    from mezbench.tests import faults

    def no_host_labels(*a, **k):
        raise ImportError("label on the device")
    monkeypatch.setattr(GE, "_label_host", no_host_labels)

    def size(cell):
        cell.config.update(characterization_clip=8)
    sound = _run(capsys, "testbed.onboard", patch=size, seconds=0.1)
    for k in ("accuracy_err", "kept_settings_off"):
        assert sound["checks"][k]["value"] == 0, sound["checks"]
    monkeypatch.setattr(GE, "_label_group", faults.label_one_round_short())
    res = _run(capsys, "testbed.onboard", patch=size, seconds=0.1)
    assert res["correct"] is False
    checks = res["checks"]
    assert (checks["accuracy_err"]["value"] > 0
            or checks["kept_settings_off"]["value"] > 0)


def test_exact_payload_matches_kernel_bit_for_bit():
    from repro.core import grid_engine as GE
    from repro.kernels import frame_knobs as FK
    from mezbench import scene

    cfg = {"num_cameras": 1, "frame_height": 48, "frame_width": 64,
           "dynamics": "complex"}
    st = scene.make_streams(SEED, cfg, 6, count=1)[0]
    fj, pj, bj, ej = GE.stage_clip(st.background, st.frames)
    n = len(st.frames) + 1
    for res, cs in ((1, 2), (3, 0), (4, 1)):
        plan = FK.build_transform_plan(48, 64, scale=RK.RESOLUTION_SCALES[res],
                                       cs=cs, blur_ks=RK.BLUR_KERNELS,
                                       art_modes=(0, 1, 2))
        pay, _, _ = FK.frame_knob_grid(fj, pj, plan, background=bj,
                                       art_enable=ej, interpret=True)
        stack = np.stack([st.background] + st.frames)
        enable = np.r_[False, np.ones(n - 1, bool)]
        for si in (0, 7, 14):
            art, blur = divmod(si, len(RK.BLUR_KERNELS))
            want = RK.exact_payload(stack, st.background, res, cs, blur, art,
                                    enable=enable)
            np.testing.assert_array_equal(np.asarray(pay[si, :n]), want)


def test_kernel_cost_counts_bytes_from_shapes():
    b, o = kernel_cost.group_cost(144, 256, 72, 128, 3, 144, 33, 5, 3)
    assert b == 33 * 144 * 256 * 3 + 15 * 33 * (3 * 72 * 128 + 28)
    assert o > 0


def test_trace_reduce_on_recorded_trace():
    path = os.path.join(HERE, "fixtures", "small.xplane.pb")
    s = trace_reduce.reduce_file(path)
    assert 0 < s.busy_s <= s.window_s
    grid, n_grid = s.module_seconds("_grid_call")
    label, n_label = s.module_seconds("_label_group")
    assert grid > 0 and n_grid == 1
    assert label > 0 and n_label == 1
    bd = s.breakdown()
    assert 0 < len(bd["device_ops"]) <= 10
    assert all(name.startswith("mezbench.") or name == "outside spans"
               for name, _ in bd["idle_gaps"])
