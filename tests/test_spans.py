"""Program spans (``mez.*``): the served path and characterization name
their layers on the profiler's clock, and ``mezbench/spans.py`` nests
them by containment and sums their self time."""

import os
import sys
from types import SimpleNamespace

import jax
import numpy as np
import pytest

from repro.core import knobs as K
from repro.core.api import QosBounds, SubscriptionOptions
from repro.core.broker import MezSystem
from repro.core.channel import calibrated_channel
from repro.core.characterization import characterize, fit_latency_regression
from repro.core.session import MezClient
from repro.data.camera import CameraConfig, SyntheticCamera

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from mezbench import spans as S  # noqa: E402

GROUPS = len(K.RESOLUTION_SCALES) * len(K.COLORSPACES)


def _camera(cid="cam0"):
    return SyntheticCamera(CameraConfig(camera_id=cid, height=48, width=64,
                                        dynamics="medium", seed=7))


def _traced(directory, fn):
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    jax.profiler.start_trace(str(directory), profiler_options=opts)
    try:
        out = fn()
    finally:
        jax.profiler.stop_trace()
    return out, S.reduce_dir(str(directory))


@pytest.fixture(scope="module")
def char_trace(tmp_path_factory):
    """A small batched characterization, traced."""
    return _traced(tmp_path_factory.mktemp("char"), lambda: characterize(
        _camera, clip_len=6, engine="batched"))


def _assert_sane(stats):
    for name, st in stats.items():
        assert st.count > 0, name
        assert 0.0 <= st.self_s <= st.seconds + 1e-12, (name, st)


def test_fleet_session_spans_nest_in_the_poll(char_trace, tmp_path):
    table, _ = char_trace
    ch = calibrated_channel(seed=3)
    system = MezSystem(ch)
    sizes = np.linspace(table.sizes_sorted[0], table.sizes_sorted[-1], 12)
    reg = fit_latency_regression(sizes, ch.regression_points(sizes, n=2))
    for i in range(2):
        cam = system.add_camera(f"cam{i}")
        src = _camera(f"cam{i}")
        cam.background = src.background
        cam.set_target(0.100, 0.90, table, reg)
        for ts, f, _ in src.stream(8):
            cam.publish(ts, f)
    sess = MezClient(system).open_session("app")
    sub = sess.subscribe(["cam0", "cam1"], 0.0, 100.0,
                         qos=QosBounds(0.1, 0.9),
                         options=SubscriptionOptions(fleet=True))

    def serve():
        polls = 0
        while sub.poll(max_frames=4):
            polls += 1
        return polls + 1                  # the last, empty poll
    polls, found = _traced(tmp_path, serve)
    stats = found.stats
    assert {"mez.poll", "mez.fleet_tick", "mez.fleet_tick.wait",
            "mez.fetch"} <= set(stats)
    assert stats["mez.poll"].count == polls
    assert stats["mez.poll"].parents == {S.TOP}
    assert stats["mez.fetch"].parents == {"mez.poll"}
    assert stats["mez.fleet_tick"].parents == {"mez.poll"}
    assert stats["mez.fleet_tick.wait"].parents == {"mez.fleet_tick"}
    _assert_sane(stats)
    # the poll's direct children and its self time make up the poll
    poll = stats["mez.poll"]
    children = sum(st.seconds for st in stats.values()
                   if "mez.poll" in st.parents)
    assert poll.self_s + children == pytest.approx(poll.seconds, rel=1e-9)
    assert found.gaps == []               # the CPU has no device plane


def test_characterization_spans_nest_in_the_sweep(char_trace):
    _, found = char_trace
    stats = found.stats
    assert stats["mez.char"].count == 1
    assert stats["mez.char"].parents == {S.TOP}
    for name in ("mez.char.wait", "mez.char.label", "mez.char.boxes",
                 "mez.char.calib", "mez.char.score"):
        assert stats[name].parents == {"mez.char"}, name
    for name in ("mez.char.wait", "mez.char.label", "mez.char.calib"):
        assert stats[name].count == GROUPS, name
    assert stats["mez.char.boxes"].count == 2 * GROUPS
    # the proxy fit in the grid and the table's scoring
    assert stats["mez.char.score"].count == 2
    _assert_sane(stats)


def test_nesting_self_time_and_gap_labels_on_hand_built_spans():
    main = [(0, 100, "mez.poll"), (10, 30, "mez.fleet_tick"),
            (20, 25, "mez.fleet_tick.wait"), (40, 60, "mez.fetch"),
            (45, 50, "mez.transform"), (70, 90, "mez.fetch"),
            (200, 260, "mez.poll"), (210, 250, "mez.fetch")]
    other = [(15, 95, "mez.char")]        # another thread: no nesting
    stats = S.span_stats([main, other], (0, 1000))
    ns = 1e-9
    assert stats["mez.poll"].count == 2
    assert stats["mez.poll"].seconds == pytest.approx(160 * ns)
    assert stats["mez.poll"].self_s == pytest.approx((40 + 20) * ns)
    assert stats["mez.fetch"].self_s == pytest.approx((15 + 20 + 40) * ns)
    assert stats["mez.fleet_tick"].self_s == pytest.approx(15 * ns)
    assert stats["mez.transform"].parents == {"mez.fetch"}
    assert stats["mez.char"].parents == {S.TOP}
    assert stats["mez.char"].self_s == pytest.approx(80 * ns)

    # clipped to the window: the first poll keeps 50..100 and the fetch
    # 50..60; the transform ends where the window starts and is dropped
    clipped = S.span_stats([main], (50, 1000))
    assert clipped["mez.poll"].seconds == pytest.approx((50 + 60) * ns)
    assert clipped["mez.poll"].self_s == pytest.approx((20 + 20) * ns)
    assert "mez.transform" not in clipped and "mez.fleet_tick" not in clipped

    bench = [(0, 1000, "mezbench.window"), (0, 100, "mezbench.poll"),
             (300, 400, "mezbench.wait")]
    assert S.label(bench, main, 22) == "mezbench.poll/mez.fleet_tick.wait"
    assert S.label(bench, main, 35) == "mezbench.poll/mez.poll"
    assert S.label(bench, main, 350) == "mezbench.wait"
    assert S.label(bench, main, 150) == "outside spans"

    run = SimpleNamespace(delivered=4)
    layers = S.layer_numbers(S.Spans(stats, []), run)
    assert layers["session_self_ms"] == pytest.approx(30 * ns * 1e3)
    assert layers["fleet_tick_host_us"] == pytest.approx(20 * ns * 1e6)
    assert layers["fetch_ms"] == pytest.approx(80 / 4 * ns * 1e3)
    assert layers["transform_ms"] == pytest.approx(5 / 4 * ns * 1e3)
    assert "deflate_ms" not in layers and "char_host_ms" not in layers
