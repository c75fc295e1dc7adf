"""Onboarding cells: cameras join back to back, each characterized over the
full knob grid through ``characterize(engine="batched")`` -- what a user
waits for when a camera joins, and what a drift refresh stalls a poll for.

Traffic parameters (``traffic/<name>.json`` with ``"runner": "onboard"``):

  scene_seed     sweep i characterizes scene i of the set drawn from this
                 seed (``scene.make_streams(scene_seed, ..., first_index=i)``):
                 the same scenes, in the same order, on every run
  check_groups   (resolution, colorspace) groups of the window's first
                 sweep, drawn from the run's seed, whose payloads are
                 compared byte for byte; every group's proxy features and
                 the whole table are compared

Sweep i takes its scene's 32 frames in an order drawn from ``(seed, i)``.
The device labeler's work follows the largest connected component in any
of a group's images, so the scene, not the order, sets a sweep's cost:
scenes are drawn from the sweep index alone so that every seed does the
same work.  Set-up sweeps scene 0 once (it compiles every group's
programs).  Whole sweeps run back to back; the sweep in flight when the
window closes is finished.
"""

from __future__ import annotations

import dataclasses
import time

import numpy as np

from . import scene
from .reference import char as RCH
from .reference import knobs as RK


@dataclasses.dataclass
class OnboardRun:
    setup_s: float
    window_s: float
    sweeps_s: list[float]
    plans: list                     # (res, cs) group geometry of one sweep
    clip_len: int
    config: dict
    summary: object = None
    attempted: int = 0
    failed: int = 0


class _Capture:
    """Keeps what the grid calls of the armed sweep return: every group's
    proxy features, and the payloads of the groups asked for, each copied
    to the host as soon as the device has it."""

    def __init__(self, fn, groups):
        self.fn, self.groups = fn, set(groups)
        self.armed = False
        self.calls = 0
        self.feats, self.payloads = [], {}

    def __call__(self, *args, **kwargs):
        out = self.fn(*args, **kwargs)
        if self.armed:
            out[1].copy_to_host_async()
            self.feats.append(out[1])
            if self.calls in self.groups:
                out[0].copy_to_host_async()
                self.payloads[self.calls] = out[0]
            self.calls += 1
        return out


def _characterize(stream, cfg, control: bool):
    from repro.core import grid_engine
    from repro.core.characterization import characterize, table_from_grid

    n = cfg["characterization_clip"]
    if not control:
        return characterize(lambda: scene.ClipCamera(stream, n), clip_len=n,
                            engine="batched",
                            include_artifact=cfg["include_artifact"],
                            min_accuracy=cfg["min_accuracy"])
    # the control: the program's own XLA transform path in place of the
    # exact Pallas kernel (default matmul precision on the chip)
    clip = stream.clip(n)
    grid = grid_engine.run_grid(stream.background, [f for _, f, _ in clip],
                                include_artifact=cfg["include_artifact"],
                                use_pallas=False)
    return table_from_grid(grid, [g for *_, g in clip],
                           min_accuracy=cfg["min_accuracy"],
                           include_artifact=cfg["include_artifact"])


def run(cell, window_factory, t_start: float, *, control: bool = False):
    import jax
    import jax.profiler as prof
    from repro.core import grid_engine
    from repro.kernels import frame_knobs

    cfg, tr, seed = cell.config, cell.traffic, cell.seed
    n = cfg["characterization_clip"]
    rng = np.random.default_rng(np.random.SeedSequence([seed, 11]))
    n_groups = len(RK.RESOLUTION_SCALES) * len(RK.COLORSPACES)
    groups = sorted(int(g) for g in rng.choice(
        n_groups, size=int(tr["check_groups"]), replace=False))
    if control:
        cap = _Capture(grid_engine._transform_group, groups)
        grid_engine._transform_group = cap
    else:
        cap = _Capture(frame_knobs.frame_knob_grid, groups)
        frame_knobs.frame_knob_grid = cap

    def camera(i: int) -> scene.CameraStream:
        base = scene.make_streams(int(tr["scene_seed"]), cfg, n,
                                  first_index=i, count=1)[0]
        order = np.random.default_rng(
            np.random.SeedSequence([seed, i])).permutation(n)
        return scene.CameraStream(base.camera_id, base.background,
                                  [base.frames[j] for j in order],
                                  [base.boxes[j] for j in order])

    # set-up: one sweep compiles every group's programs
    _characterize(camera(0), cfg, control)
    setup_s = time.perf_counter() - t_start

    sweeps, tables, streams = [], [], []
    failed = 0
    with window_factory() as win:
        end = win.t0 + cell.seconds
        i = 0
        while i == 0 or time.perf_counter() < end:
            st = camera(i)
            cap.armed = i == 0
            t0 = time.perf_counter()
            with prof.TraceAnnotation("mezbench.sweep"):
                table = _characterize(st, cfg, control)
            sweeps.append(time.perf_counter() - t0)
            if not table.settings:
                failed += 1
            if i == 0:
                tables.append(table)
                streams.append(st)
            i += 1
    cap.armed = False
    kept = {"feats": [np.asarray(f) for f in cap.feats],
            "payloads": {g: np.asarray(p) for g, p in cap.payloads.items()}}
    cap.feats, cap.payloads = [], {}
    if control:
        grid_engine._transform_group = cap.fn
    else:
        frame_knobs.frame_knob_grid = cap.fn
    h, w = cfg["frame_height"], cfg["frame_width"]
    plans = [(res, cs, RK.exact_geometry(h, w, res, cs))
             for res in range(len(RK.RESOLUTION_SCALES))
             for cs in range(len(RK.COLORSPACES))]
    run_ = OnboardRun(setup_s=setup_s, window_s=win.seconds,
                      sweeps_s=sweeps, plans=plans, clip_len=n, config=cfg,
                      attempted=len(sweeps), failed=failed)
    return run_, {"table": tables[0], "stream": streams[0], "kept": kept,
                  "cfg": cfg, "win": win}


def check(state: dict) -> dict:
    """Numbers compared with the reference characterization of the
    window's first sweep: the payload bytes of the drawn groups, every
    group's proxy features, and the kept table (which settings, their
    sizes and accuracies: the labeler's output reaches the table only
    through the accuracies)."""
    cfg, st, kept = state["cfg"], state["stream"], state["kept"]
    n = cfg["characterization_clip"]
    out = {"payload_bytes_off": 0, "feature_rel_err": 0.0}
    n_cs = len(RK.COLORSPACES)

    def on_group(res, cs, pay, feats):
        g = res * n_cs + cs
        if g >= len(kept["feats"]):
            out["feature_rel_err"] = float("inf")
            return
        got = np.asarray(kept["feats"][g], np.float64)[:, :n + 1]
        if got.shape != feats.shape:
            out["feature_rel_err"] = float("inf")
        else:
            out["feature_rel_err"] = max(out["feature_rel_err"], float(
                np.max(np.abs(got - feats) / np.maximum(np.abs(feats), 1.0))))
        if g in kept["payloads"]:
            p = kept["payloads"][g][:, :n + 1]
            out["payload_bytes_off"] += int(
                pay.size if p.shape != pay.shape else (p != pay).sum())

    ref = RCH.characterize(st.background, st.clip(n),
                           include_artifact=cfg["include_artifact"],
                           min_accuracy=cfg["min_accuracy"],
                           on_group=on_group)
    table = state["table"]
    prog = {(s.resolution, s.colorspace, s.blur, s.artifact, s.diff):
            (float(z), float(a)) for s, z, a in
            zip(table.settings, table.size_by_setting, table.acc_by_setting)}
    common = set(prog) & set(ref.kept)
    out["size_rel_err"] = max((abs(prog[k][0] - ref.kept[k][0])
                               / ref.kept[k][0] for k in common), default=0.0)
    out["accuracy_err"] = max((abs(prog[k][1] - ref.kept[k][1])
                               for k in common), default=0.0)
    out["kept_settings_off"] = len(set(prog) ^ set(ref.kept))
    out["kept"] = len(ref.kept)
    return out
