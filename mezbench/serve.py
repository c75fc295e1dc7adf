"""Serving cells: cameras publish open-loop on a schedule, tenants poll
through ``MezClient`` sessions, and every delivery is recorded for the
metrics and for the check against the plain reference.

Traffic parameters (``traffic/<name>.json`` with ``"runner": "serve"``):

  tenants              list of tenant groups, each ``{"count": n,
                       "cameras": ...}``: a session per tenant, subscribed
                       with ``SubscriptionOptions(fleet=True)`` to
                       ``"all"`` cameras, to a list of camera indices, or
                       to ``{"draw": m, "zipf": a}``: m cameras drawn per
                       tenant from the seed, camera i with weight
                       ``(i + 1) ** -a``
  schedule             optional list of phases ``{"seconds": d, "rate":
                       r}``, repeated in order from the window's start:
                       the cameras publish at r times the configuration's
                       fps (0: not at all) for d seconds.  Default: the
                       configuration's fps throughout
  warmup_ticks         publish ticks polled during set-up (compiles each
                       subscription's fused tick before the window opens)
  check_subscriptions  subscriptions, drawn from the seed, whose every
                       delivery the reference replays and whose received
                       frames are scored for accuracy
  scene_seed           optional: the cameras' scenes are drawn from it
                       alone, the same on every run's seed, which deals
                       scenes 1.. to cameras 1.. (camera 0 keeps scene 0).
                       Default: scenes drawn from the run's seed

Every camera appends one frame per tick, ticks synchronized.  A
subscription is polled only while each of its cameras holds a frame it has
not fetched (a camera with no unfetched frame is marked drained),
round-robin over those that do, with a budget of ``cameras *
fetch_window`` frames so that every camera is fetched on every poll.
"""

from __future__ import annotations

import dataclasses
import math
import time

import numpy as np

from . import scene
from .reference import accuracy as RA
from .reference import char as RCH
from .reference import control as RC
from .reference import knobs as RK


@dataclasses.dataclass
class ServeRun:
    """What a serving run measured (the metric readers' input)."""
    setup_s: float
    window_s: float
    latencies_s: list[float]          # due -> returned, shipped frames
    delivered: int                    # shipped frames in the window
    lags_s: list[float]               # publish time - due time
    poll_s: list[float]               # host time of each window poll
    cache_misses: int                 # transforms computed in the window
    summary: object = None            # trace_reduce.Summary (trace runs)
    attempted: int = 0
    failed: int = 0


def tick_times(fps: float, schedule, n: int) -> list[float]:
    """Publish times (seconds from the first tick) of ``n`` ticks."""
    if not schedule:
        return [k / fps for k in range(n)]
    phases = [(float(p["seconds"]), float(p["rate"]))
              for p in schedule]
    if not any(d > 0 and r > 0 for d, r in phases):
        raise ValueError("the schedule never publishes")
    out, t0, last, i = [], 0.0, None, 0
    while len(out) < n:
        dur, rate = phases[i % len(phases)]
        if rate > 0:
            step = 1.0 / (fps * rate)
            t = t0 if last is None else max(t0, last + step)
            while t < t0 + dur - 1e-12 and len(out) < n:
                out.append(t)
                last = t
                t += step
        t0 += dur
        i += 1
    return out


def cameras(seed: int, traffic: dict, cfg: dict, n: int
            ) -> list[scene.CameraStream]:
    """The cameras' streams.  Drawn from the run's seed, or, with
    ``scene_seed``, the same scenes on every seed: camera 0 (whose clip
    makes the table) shows scene 0, and the seed deals the other scenes
    to the other cameras."""
    if traffic.get("scene_seed") is None:
        return scene.make_streams(seed, cfg, n)
    base = scene.make_streams(int(traffic["scene_seed"]), cfg, n)
    rng = np.random.default_rng(np.random.SeedSequence([seed, 17]))
    order = [0] + [1 + int(j) for j in rng.permutation(len(base) - 1)]
    return [scene.CameraStream(f"cam{i}", base[j].background, base[j].frames,
                               base[j].boxes) for i, j in enumerate(order)]


def camera_sets(groups, n_cams: int, seed: int) -> list[list[int]]:
    """Each tenant's cameras, in tenant order."""
    rng = np.random.default_rng(np.random.SeedSequence([seed, 13]))
    out = []
    for g in groups:
        cams = g["cameras"]
        for _ in range(int(g["count"])):
            if cams == "all":
                out.append(list(range(n_cams)))
            elif isinstance(cams, dict):
                w = (np.arange(n_cams) + 1.0) ** -float(cams.get("zipf", 0))
                out.append(sorted(int(c) for c in rng.choice(
                    n_cams, size=int(cams["draw"]), replace=False,
                    p=w / w.sum())))
            else:
                out.append(sorted(int(c) for c in cams))
    return out


def run(cell, window_factory, t_start: float, *, control: bool = False
        ) -> tuple[ServeRun, dict]:
    """Set up and measure one window; returns the run and what the check
    needs."""
    import jax.profiler as prof
    from repro.core.api import QosBounds, SubscriptionOptions
    from repro.core.broker import MezSystem
    from repro.core.channel import calibrated_channel
    from repro.core.characterization import (characterize,
                                             fit_latency_regression)
    from repro.core.session import MezClient

    cfg, tr, seed = cell.config, cell.traffic, cell.seed
    n_cams, fps = cfg["num_cameras"], float(cfg["fps"])
    dist = float(cfg["distance_m"])
    warm = int(tr["warmup_ticks"])
    # enough ticks for the window at the schedule's highest rate
    peak = max([float(p["rate"]) for p in tr.get("schedule", [])] or [1.0])
    n_ticks = warm + int(math.ceil(cell.seconds * fps * peak)) + 2
    times = tick_times(fps, tr.get("schedule"), n_ticks)
    clip = cfg["characterization_clip"]
    streams = cameras(seed, tr, cfg, max(n_ticks, clip))
    # the table is characterized on camera 0's own first frames (the
    # paper characterizes a camera offline on its footage) and installed
    # on every camera
    calib = streams[0]
    table = characterize(lambda: scene.ClipCamera(calib, clip),
                         clip_len=clip, engine="batched",
                         include_artifact=cfg["include_artifact"],
                         min_accuracy=cfg["min_accuracy"])
    names = [(s.resolution, s.colorspace, s.blur, s.artifact, s.diff)
             for s in table.settings]
    channel = calibrated_channel(seed=seed, workload=cfg["channel_workload"])
    system = MezSystem(channel)
    sizes = np.linspace(table.sizes_sorted[0], table.sizes_sorted[-1], 16)
    regression = fit_latency_regression(sizes, channel.regression_points(
        sizes, n=n_cams, fps=fps, distance_m=dist))
    cams = []
    for st in streams:
        cam = system.add_camera(st.camera_id, distance_m=dist, fps=fps)
        cam.background = st.background
        cam.set_target(cfg["latency_target"], cfg["accuracy_target"], table,
                       regression)
        cams.append(cam)
    ids = [st.camera_id for st in streams]
    tick_of = {t: k for k, t in enumerate(times)}
    cam_index = {cid: i for i, cid in enumerate(ids)}
    client = MezClient(system)
    cams_of = camera_sets(tr["tenants"], n_cams, seed)
    n_subs = len(cams_of)
    sessions = [client.open_session(f"app{j}") for j in range(n_subs)]
    opts = SubscriptionOptions(fleet=True,
                               feedback_window=cfg["feedback_window"],
                               credit_limit=cfg["fetch_window"])
    qos = QosBounds(cfg["latency_target"], cfg["accuracy_target"])
    subs = [sess.subscribe([ids[c] for c in cs], 0.0, 1e9, qos=qos,
                           options=opts)
            for sess, cs in zip(sessions, cams_of)]
    rng = np.random.default_rng(np.random.SeedSequence([seed, 7]))
    sample = set(int(x) for x in rng.choice(
        n_subs, size=min(n_subs, int(tr["check_subscriptions"])),
        replace=False))
    budgets = [len(cs) * cfg["fetch_window"] for cs in cams_of]

    polls: list[RC.Poll] = []
    fetched = [0] * n_subs            # next unfetched tick, per sub
    n_polls = [0] * n_subs
    published = 0
    failed = 0

    def publish(k: int) -> None:
        nonlocal published
        for st, cam in zip(streams, cams):
            cam.publish(times[k], st.frames[k])
        published = k + 1

    def poll(s: int):
        nonlocal failed
        t0 = time.perf_counter()
        batch = subs[s].poll(max_frames=budgets[s])
        t1 = time.perf_counter()
        keep = s in sample
        got = [RC.Delivery(cam_index[d.camera_id], tick_of[d.timestamp],
                           d.frame if keep else None,
                           names[d.knob_index] if d.knob_index >= 0
                           else None, int(d.wire_bytes))
               for d in batch.frames]
        polls.append(RC.Poll(s, n_polls[s], got, published))
        n_polls[s] += 1
        if not got or len(got) % len(cams_of[s]):
            failed += 1
        if got:
            fetched[s] = max(d.tick for d in got) + 1
        return t0, t1, got

    for k in range(warm):
        publish(k)
        for s in range(n_subs):
            poll(s)
    setup_s = time.perf_counter() - t_start

    cache = system.edge.frame_cache
    miss0 = cache.misses
    lats, lags, poll_s = [], [], []
    delivered = attempted = failed = 0
    k, rr = warm, 0
    with window_factory() as win:
        end = win.t0 + cell.seconds
        due = lambda j: win.t0 + times[j] - times[warm]
        while True:
            now = time.perf_counter()
            if now >= end:
                break
            if k < n_ticks and due(k) <= now:
                with prof.TraceAnnotation("mezbench.publish"):
                    while k < n_ticks and due(k) <= now:
                        publish(k)
                        lags.append(time.perf_counter() - due(k))
                        k += 1
            pick = None
            for j in range(n_subs):
                s = (rr + j) % n_subs
                if fetched[s] < k:
                    pick = s
                    break
            if pick is None:
                wait = min(due(k) if k < n_ticks else end, end) - now
                if wait > 0:
                    with prof.TraceAnnotation("mezbench.wait"):
                        time.sleep(wait)
                continue
            rr = pick + 1
            attempted += 1
            with prof.TraceAnnotation("mezbench.poll"):
                t0, t1, got = poll(pick)
            poll_s.append(t1 - t0)
            for d in got:
                if d.wire > 0:
                    delivered += 1
                    lats.append(t1 - due(d.tick))
    misses = cache.misses - miss0
    for sess in sessions:
        sess.close()
    run_ = ServeRun(setup_s=setup_s, window_s=win.seconds, latencies_s=lats,
                    delivered=delivered, lags_s=lags, poll_s=poll_s,
                    cache_misses=misses,
                    attempted=attempted, failed=failed)
    return run_, {"cfg": cfg, "seed": seed, "calib": calib,
                  "streams": streams, "polls": polls, "n_subs": n_subs,
                  "cams_of": cams_of, "sample": sample, "win": win,
                  "control": control}


def check(state: dict) -> dict:
    """The numbers compared with the reference (``limits/serve.json``):
    the reference characterizes the calibration clip itself and replays
    every poll of the sampled subscriptions against its own table, and
    scores what those subscriptions received.  With the control on, the
    replay's transforms are also run in bfloat16 (``control.*``)."""
    cfg, streams = state["cfg"], state["streams"]
    clip = cfg["characterization_clip"]
    calib = state["calib"]
    ref = RCH.characterize(calib.background, calib.clip(clip),
                           include_artifact=cfg["include_artifact"],
                           min_accuracy=cfg["min_accuracy"])
    dep = RC.Deployment(
        table=RC.Table.from_kept(ref.kept, ref.coeffs,
                                 RK.settings(cfg["include_artifact"])),
        frames=[st.frames for st in streams],
        backgrounds=[st.background for st in streams],
        channel=RC.Channel(cfg["channel_workload"], cfg["num_cameras"]),
        channel_seed=state["seed"], fps=float(cfg["fps"]),
        distance=float(cfg["distance_m"]),
        latency_target=cfg["latency_target"],
        accuracy_target=cfg["accuracy_target"],
        feedback_window=cfg["feedback_window"],
        log_capacity=int(cfg["log_capacity"]))
    args = (dep, state["polls"], state["n_subs"], state["cams_of"],
            state["sample"])
    out = RC.check_serving(*args)
    rows = []
    for p in state["polls"]:
        if p.sub not in state["sample"]:
            continue
        for d in p.deliveries:
            st = streams[d.cam]
            rows.append((st.boxes[d.tick], st.frames[d.tick], d.payload,
                         d.setting, st.background))
    f1 = RA.normalized_f1(rows)
    out["f1_norm"] = float("nan") if f1 is None else f1
    out["kept"] = len(ref.kept)
    if state["control"]:
        lower = RC.check_serving(*args, lower=True)
        out.update({f"control.{k}": v for k, v in lower.items()})
    return out
