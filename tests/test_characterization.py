"""Batched characterization engine vs the per-frame reference oracle (knob4
included), the wire-size proxy's calibration bound, online
re-characterization (``refresh_tables`` / ``CamBroker.recharacterize``),
the broker's pre-screen, and the knob-pipeline satellites (YUV packing
round-trip, transform memo, broker payload reuse)."""

import zlib

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import detector as det
from repro.core import grid_engine
from repro.core import knobs as K
from repro.core.broker import TABLE_CAPACITY, CamBroker, MezSystem
from repro.core.channel import calibrated_channel
from repro.core.characterization import characterize, fit_latency_regression
from repro.data.camera import CameraConfig, SyntheticCamera
from repro.kernels import frame_knobs as FK

CAMF = lambda: SyntheticCamera(CameraConfig(dynamics="medium", seed=7))
CLIP_LEN = 8


@pytest.fixture(scope="module")
def tables():
    return (characterize(CAMF, clip_len=CLIP_LEN, engine="batched"),
            characterize(CAMF, clip_len=CLIP_LEN, engine="reference"))


@pytest.fixture(scope="module")
def grid():
    cam = CAMF()
    bg = cam.background
    clip = [cam.next_frame() for _ in range(CLIP_LEN)]
    return bg, clip, grid_engine.run_grid(bg, [f for _, f, _ in clip])


class TestEngineEquivalence:
    def test_kept_settings_agree(self, tables):
        batched, reference = tables
        sb, sr = set(batched.settings), set(reference.settings)
        # proxy sizes can flip settings hovering exactly at the accuracy /
        # size boundaries; the characterized set must still agree broadly
        assert len(sb & sr) >= 0.9 * max(len(sb), len(sr))

    def test_accuracies_agree(self, tables):
        batched, reference = tables
        accb = {s: a for s, a in zip(batched.settings,
                                     batched.acc_by_setting)}
        accr = {s: a for s, a in zip(reference.settings,
                                     reference.acc_by_setting)}
        shared = set(accb) & set(accr)
        diffs = np.asarray([abs(accb[s] - accr[s]) for s in shared])
        # detector scoring is the same algorithm batched: identical up to
        # f32-vs-f64 threshold rounding on a handful of border pixels
        assert np.median(diffs) == 0.0
        assert diffs.max() <= 0.05

    def test_sizes_within_proxy_tolerance(self, tables):
        batched, reference = tables
        szb = {s: v for s, v in zip(batched.settings,
                                    batched.size_by_setting)}
        szr = {s: v for s, v in zip(reference.settings,
                                    reference.size_by_setting)}
        shared = set(szb) & set(szr)
        rel = np.asarray([abs(szb[s] - szr[s]) / szr[s] for s in shared])
        assert np.median(rel) < 0.10

    def test_deterministic(self):
        a = characterize(CAMF, clip_len=4, engine="batched")
        b = characterize(CAMF, clip_len=4, engine="batched")
        assert a.settings == b.settings
        np.testing.assert_array_equal(a.sizes_sorted, b.sizes_sorted)
        np.testing.assert_array_equal(a.best_acc, b.best_acc)

    def test_residual_spread_measured_and_quiet(self, tables):
        """Both engines report the calibration clip's wire-size residual
        spread, and on the synthetic clips it stays well under the drift
        floor -- learned hysteresis falls back to the proven constants, so
        characterization changes never perturb the committed goldens."""
        from repro.core.drift import (SPREAD_MULTIPLE, DriftConfig,
                                      learned_thresholds)
        base = DriftConfig()
        for tbl in tables:
            assert tbl.residual_spread is not None
            assert np.isfinite(tbl.residual_spread)
            assert 0.0 < tbl.residual_spread < base.hi / SPREAD_MULTIPLE
            assert learned_thresholds(tbl.residual_spread) == (base.hi,
                                                               base.lo)

    def test_auto_covers_artifact_knob_batched(self):
        """knob4 no longer forces the reference fallback: auto resolves to
        the batched engine and still characterizes artifact settings."""
        tbl = characterize(CAMF, clip_len=3, include_artifact=True,
                           min_accuracy=0.0)
        assert any(s.artifact > 0 for s in tbl.settings)
        assert tbl.proxy is not None       # batched-engine fingerprint

    def test_controller_closed_loop_on_batched_table(self, tables):
        """The proxy-sized table drives the PI loop to its latency bound."""
        from repro.core.controller import ControllerConfig, LatencyController
        batched, _ = tables
        ch = calibrated_channel(seed=3, workload="jaad")
        sizes = np.linspace(batched.sizes_sorted[0], batched.sizes_sorted[-1],
                            12)
        reg = fit_latency_regression(sizes, ch.regression_points(sizes, n=2))
        c = LatencyController(ControllerConfig(0.100, 0.90), batched, reg)
        ch.activate("cam0")
        size = batched.size_by_setting[c._current]
        lats = []
        for _ in range(25):
            lat = ch.transfer(float(size))
            lats.append(lat)
            d = c.update(lat)
            if d.setting_index >= 0:
                size = batched.size_by_setting[d.setting_index]
        assert np.percentile(lats[8:], 95) < 0.14


class TestKnob4Equivalence:
    """knob4 on device: ``characterize(engine='batched',
    include_artifact=True)`` against the NumPy reference oracle."""

    @pytest.fixture(scope="class")
    def art_tables(self):
        return (characterize(CAMF, clip_len=6, engine="batched",
                             include_artifact=True),
                characterize(CAMF, clip_len=6, engine="reference",
                             include_artifact=True))

    def test_kept_settings_identical(self, art_tables):
        batched, reference = art_tables
        assert set(batched.settings) == set(reference.settings)

    def test_accuracies_agree(self, art_tables):
        batched, reference = art_tables
        accb = dict(zip(batched.settings, batched.acc_by_setting))
        accr = dict(zip(reference.settings, reference.acc_by_setting))
        diffs = np.asarray([abs(accb[s] - accr[s])
                            for s in set(accb) & set(accr)])
        assert np.median(diffs) == 0.0
        assert diffs.max() <= 0.05

    def test_artifact_settings_scored(self):
        """The batched engine actually scores knob4 settings (visible with
        the accuracy floor dropped) instead of skipping them."""
        tbl = characterize(CAMF, clip_len=3, engine="batched",
                           include_artifact=True, min_accuracy=0.0)
        art = [s for s in tbl.settings if s.artifact > 0]
        assert len(art) > 0
        assert tbl.proxy is not None

    def test_odd_geometry_raises_clear_error(self):
        """Regression: engine='batched' must REFUSE unsupported odd
        geometry loudly -- the seed behaviour was a silent minutes-long
        fallback to the reference path."""
        camf = lambda: SyntheticCamera(CameraConfig(
            dynamics="medium", seed=7, height=30, width=41))
        with pytest.raises(ValueError, match="even-dimension"):
            characterize(camf, clip_len=2, engine="batched")
        # the error must point at the escape hatches
        try:
            characterize(camf, clip_len=2, engine="batched")
        except ValueError as e:
            assert "reference" in str(e) and "auto" in str(e)


class TestOnlineRecharacterization:
    def test_refresh_tables_pseudo_gt(self):
        """``refresh_tables`` characterizes an unlabeled live clip: the
        full-quality detections act as ground truth, so the unmodified
        setting scores accuracy 1.0 and the table is controller-ready."""
        cam = CAMF()
        bg = cam.background
        clip = [cam.next_frame()[1] for _ in range(6)]
        table, jt = grid_engine.refresh_tables(bg, clip, capacity=64)
        assert len(table.settings) > 0
        assert table.proxy is not None
        full = table.settings.index(K.KnobSetting(0, 0, 0, 0, 0))
        np.testing.assert_allclose(table.acc_by_setting[full], 1.0)
        assert jt.sizes_sorted.shape[0] == 64
        assert int(jt.n_valid) == len(table.settings)
        assert np.isinf(np.asarray(jt.sizes_sorted)[int(jt.n_valid):]).all()

    def test_cambroker_recharacterize_swaps_live_tables(self, tables):
        batched, _ = tables
        ch = calibrated_channel(seed=3)
        sys = MezSystem(ch)
        cam = sys.add_camera("cam0")
        src = CAMF()
        cam.background = src.background
        sizes = np.linspace(batched.sizes_sorted[0],
                            batched.sizes_sorted[-1], 8)
        reg = fit_latency_regression(sizes, ch.regression_points(sizes, n=1))
        cam.set_target(0.1, 0.9, batched, reg)
        v0 = cam.table_version
        assert cam.jax_tables is not None          # installed by set_target
        for ts, f, _ in src.stream(6):
            cam.publish(ts, f)
        assert cam.recharacterize(clip_len=6)
        assert cam.table_version == v0 + 1
        assert cam.controller.table is not batched
        assert cam.controller.table.proxy is not None
        assert int(cam.jax_tables.n_valid) == len(cam.controller.table.settings)
        assert cam.jax_tables.sizes_sorted.shape[0] >= TABLE_CAPACITY
        # the refreshed table still drives fetch end to end
        out = cam.fetch(0.0, 10.0, latency_feedback=0.1)
        assert any(d.frame is not None for d in out)

    def test_recharacterize_without_state_is_refused(self):
        cam = CamBroker("cam0", calibrated_channel(seed=1))
        assert not cam.recharacterize()            # no controller yet

    def test_recharacterize_preserves_floor_and_knob4(self):
        """A refresh must not silently reshape the trade space: the live
        table's accuracy floor and knob4 coverage carry over by default."""
        tbl = characterize(CAMF, clip_len=4, engine="batched",
                           include_artifact=True, min_accuracy=0.0)
        assert tbl.includes_artifact
        ch = calibrated_channel(seed=3)
        sys = MezSystem(ch)
        cam = sys.add_camera("cam0")
        src = CAMF()
        cam.background = src.background
        sizes = np.linspace(tbl.sizes_sorted[0], tbl.sizes_sorted[-1], 8)
        reg = fit_latency_regression(sizes, ch.regression_points(sizes, n=1))
        cam.set_target(0.1, 0.5, tbl, reg)
        for ts, f, _ in src.stream(4):
            cam.publish(ts, f)
        assert cam.recharacterize(clip_len=4)
        fresh = cam.controller.table
        assert fresh is not tbl
        assert fresh.min_accuracy == 0.0           # floor carried over
        assert fresh.includes_artifact             # knob4 axis survived


class TestTransformGroupTwin:
    def test_honors_actual_mode_ids(self):
        """The XLA twin must key knob4 masks by the plan's ACTUAL mode ids
        (like the kernel's per-setting art_ids), not by block position --
        regression for art_modes=(0, 2) applying the movers mask to the
        contours block."""
        rng = np.random.default_rng(3)
        h, w, f = 16, 24, 2
        bg = rng.integers(40, 200, (h, w, 3)).astype(np.uint8)
        frames = np.clip(bg[None] + rng.normal(0, 5, (f, h, w, 3)),
                         0, 255).astype(np.uint8)
        frames[1, 4:10, 6:14] = 250
        prev = np.concatenate([frames[:1], frames[:-1]])
        enable = np.ones(f, np.int32)
        plan = FK.build_transform_plan(h, w, scale=1.0, cs=0,
                                       blur_ks=(0,), art_modes=(0, 2))
        from repro.kernels import ref
        pr, _, _ = ref.frame_knob_grid_ref(
            jnp.asarray(frames), jnp.asarray(prev), plan,
            background=jnp.asarray(bg), art_enable=jnp.asarray(enable))
        pt, _, _ = grid_engine._transform_group(
            jnp.asarray(frames), jnp.asarray(plan.ry),
            jnp.asarray(plan.rx), jnp.asarray(plan.bys),
            jnp.asarray(plan.bxs), 0, bg=jnp.asarray(bg),
            enable=jnp.asarray(enable), art_modes=(0, 2))
        d = np.abs(np.asarray(pt).astype(np.int32)
                   - np.asarray(pr).astype(np.int32))
        assert d.max() <= 1                        # same masks, same math


class TestWireSizePrescreen:
    def test_proxy_features_host_matches_device(self):
        rng = np.random.default_rng(5)
        frame = rng.integers(0, 256, (24, 32, 3)).astype(np.uint8)
        for cs in range(3):
            for blur in (0, 2):
                s = K.KnobSetting(1, cs, blur)
                wire = K.transform_frame(frame, s)
                got = FK.proxy_features_host(wire)
                # device layout: planes
                planes = (jnp.moveaxis(jnp.asarray(wire), -1, 0)
                          if wire.ndim == 3 else jnp.asarray(wire)[None])
                want = np.asarray(FK.proxy_features(planes))
                np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-2)

    def _broker(self, table, accuracy=0.9):
        ch = calibrated_channel(seed=3)
        sys = MezSystem(ch)
        cam = sys.add_camera("cam0")
        src = CAMF()
        cam.background = src.background
        sizes = np.linspace(table.sizes_sorted[0], table.sizes_sorted[-1], 8)
        reg = fit_latency_regression(sizes, ch.regression_points(sizes, n=1))
        cam.set_target(0.1, accuracy, table, reg)
        return cam, src

    def test_fetch_runs_prescreen_on_acting_decisions(self, tables):
        batched, _ = tables
        cam, src = self._broker(batched)
        for ts, f, _ in src.stream(4):
            cam.publish(ts, f)
        out = cam.fetch(0.0, 10.0, latency_feedback=0.25)
        assert cam.prescreen_evals > 0             # features ran in fetch
        assert all(f.knob_index >= 0 for f in out if f.frame is not None)

    def test_overshooting_candidate_steps_down(self, tables):
        """A candidate whose predicted wire size blows the controller's
        budget is stepped down the table from byte-delta features alone --
        deflate never runs on the rejected candidate."""
        from repro.core.controller import ControlDecision
        batched, _ = tables
        cam, src = self._broker(batched, accuracy=0.0)
        ts, frame, _ = src.next_frame()
        # the PI asked for the HIGHEST-fidelity setting but granted only a
        # third of its clip-median bytes (interference mid-renegotiation)
        idx = int(np.argmax(batched.size_by_setting))
        budget = float(batched.size_by_setting[idx]) * 0.3
        decision = ControlDecision(True, batched.setting_for(idx), idx,
                                   1.0, budget, 0.05, True)
        eff_setting, eff_idx, entry = cam._prescreen(ts, frame, decision)
        assert cam.prescreen_stepdowns > 0
        assert eff_idx != idx
        assert (batched.size_by_setting[eff_idx]
                < batched.size_by_setting[idx])
        # the returned entry is the ACCEPTED setting's payload, held in the
        # fleet-shared degraded-frame cache (the per-camera dict only backs
        # unregistered brokers)...
        key = (cam.camera_id, ts, eff_setting.resolution,
               eff_setting.colorspace, eff_setting.blur,
               eff_setting.artifact)
        assert cam.shared_cache._entries[key] is entry
        # ...and no deflate was paid along the walk
        assert all(e[1] is None for e in cam.shared_cache._entries.values())

    def test_prescreen_inert_without_proxy(self, tables):
        """Reference-engine tables carry no proxy: fetch must behave
        exactly as before (no evals, controller decision shipped as-is)."""
        _, reference = tables
        assert reference.proxy is None
        ch = calibrated_channel(seed=3)
        sys = MezSystem(ch)
        cam = sys.add_camera("cam0")
        src = CAMF()
        cam.background = src.background
        sizes = np.linspace(reference.sizes_sorted[0],
                            reference.sizes_sorted[-1], 8)
        reg = fit_latency_regression(sizes, ch.regression_points(sizes, n=1))
        cam.set_target(0.1, 0.9, reference, reg)
        for ts, f, _ in src.stream(4):
            cam.publish(ts, f)
        out = cam.fetch(0.0, 10.0, latency_feedback=0.25)
        assert cam.prescreen_evals == 0
        assert len(out) == 4


class TestWireSizeProxy:
    def test_median_error_vs_zlib(self, grid):
        """Acceptance bound: proxy within 10% median relative error of
        real zlib level-1 across the whole (res, cs, blur) x frame grid."""
        bg, clip, g = grid
        rels = []
        for (res, cs, b, art), pred in g.sizes.items():
            setting = K.KnobSetting(res, cs, b, art)
            for fi, (_, frame, _) in enumerate(clip):
                payload = K.transform_frame(frame, setting)
                true = len(zlib.compress(
                    np.ascontiguousarray(payload).tobytes(), 1))
                rels.append(abs(pred[fi] - true) / true)
        rels = np.asarray(rels)
        assert np.median(rels) < 0.10
        assert np.percentile(rels, 90) < 0.25
        assert g.proxy.median_rel_err < 0.10
        # deflate left the hot path: one calibration call per combo
        assert g.zlib_calls == len(g.sizes)

    def test_sizes_monotone_with_payload(self, grid):
        """Sanity: the proxy ranks a downscaled gray payload far below the
        full-resolution BGR one."""
        _, _, g = grid
        full = float(np.median(g.sizes[(0, 0, 0, 0)]))
        tiny = float(np.median(g.sizes[(4, 1, 0, 0)]))
        assert tiny < 0.25 * full


class TestDropPatterns:
    def test_match_frame_difference_walk(self, grid):
        bg, clip, g = grid
        for thresh in K.DIFF_THRESHOLDS:
            want = np.zeros(len(clip), bool)
            last = None
            for fi, (_, frame, _) in enumerate(clip):
                if K.frame_difference(frame, last, thresh):
                    want[fi] = True
                else:
                    last = frame
            np.testing.assert_array_equal(g.drop_pattern(thresh), want)


class TestSegmentBoxes:
    def test_matches_host_helper(self, grid):
        """The vectorized box extractor agrees with the per-component
        reference helper on real detector masks."""
        bg, clip, _ = grid
        for _, frame, _ in clip[:4]:
            g = frame.astype(np.float32).mean(-1)
            b = bg.astype(np.float32).mean(-1)
            diff = np.abs(g - b)
            mask = det.dilate_cross(diff > 12.0)
            labels, _ = grid_engine._label_host(mask[None])
            want = det.boxes_from_labels(labels[0], diff, background_label=0,
                                         sy=1.0, sx=1.0, min_px=4.0)
            got = grid_engine._segment_boxes(labels[0], diff,
                                             background_label=0,
                                             sy=1.0, sx=1.0, min_px=4.0)
            np.testing.assert_allclose(got, want, atol=1e-5)


class TestYuvPacking:
    @pytest.mark.parametrize("h,w", [(16, 24), (16, 25), (15, 25), (18, 33)])
    def test_round_trip_planes(self, h, w):
        """U and V planes are both fully recoverable from the packed
        payload -- the seed silently truncated V's last column when the
        frame width was odd (w < 2 * ceil(w/2))."""
        rng = np.random.default_rng(h * 100 + w)
        frame = rng.integers(0, 256, (h, w, 3)).astype(np.uint8)
        packed = K._to_colorspace(frame, "yuv420")
        uh, uw = -(-h // 2), -(-w // 2)
        pw = max(w, 2 * uw)
        assert packed.shape == (h + uh, pw)

        f = frame.astype(np.float32)
        b, g, r = f[..., 0], f[..., 1], f[..., 2]
        y = 0.114 * b + 0.587 * g + 0.299 * r
        u = np.clip(np.round(0.492 * (b - y) + 128.0), 0, 255)[::2, ::2]
        v = np.clip(np.round(0.877 * (r - y) + 128.0), 0, 255)[::2, ::2]
        np.testing.assert_array_equal(packed[h:, :uw], u.astype(np.uint8))
        np.testing.assert_array_equal(packed[h:, uw:2 * uw],
                                      v.astype(np.uint8))

    def test_even_width_layout_unchanged(self):
        """Even geometries keep the seed's exact payload (Y on top, U|V
        below, width w) -- no wire-size regression for the common case."""
        rng = np.random.default_rng(3)
        frame = rng.integers(0, 256, (12, 20, 3)).astype(np.uint8)
        packed = K._to_colorspace(frame, "yuv420")
        assert packed.shape == (12 + 6, 20)


class TestTransformMemoAndBroker:
    def test_memo_caches_per_transform_key(self):
        bg = CAMF().background
        memo = K.TransformMemo(bg)
        s1 = K.KnobSetting(1, 1, 2, 0, 0)
        s2 = K.KnobSetting(1, 1, 2, 0, 3)      # same transform, other diff
        a, b = memo.get(s1), memo.get(s2)
        assert a is b
        np.testing.assert_array_equal(a, K.transform_frame(bg, s1))

    def test_degraded_background_tracks_background(self):
        cam = CamBroker("cam0", calibrated_channel(seed=1))
        assert cam.degraded_background(K.KnobSetting()) is None
        src = CAMF()
        cam.background = src.background
        s = K.KnobSetting(2, 1, 1, 0, 0)
        np.testing.assert_array_equal(
            cam.degraded_background(s), K.transform_frame(src.background, s))
        cam.background = np.zeros_like(src.background)
        assert cam.degraded_background(s).max() == 0

    def test_payload_cache_reused_across_subscriptions(self, tables):
        """Two subscriptions fanning out from one camera share the knob
        transform work, with identical delivered payloads."""
        batched, _ = tables
        ch = calibrated_channel(seed=3)
        sys = MezSystem(ch)
        cam = sys.add_camera("cam0")
        src = CAMF()
        cam.background = src.background
        sizes = np.linspace(batched.sizes_sorted[0], batched.sizes_sorted[-1],
                            8)
        reg = fit_latency_regression(sizes, ch.regression_points(sizes, n=1))
        cam.set_target(0.1, 0.9, batched, reg)
        for ts, f, _ in src.stream(6):
            cam.publish(ts, f)
        # latency_feedback=None -> the controller's current setting is used
        # verbatim for both walks (no PI update between them)
        a = cam.fetch(0.0, 10.0)
        hits_before = cam.shared_cache.hits
        b = cam.fetch(0.0, 10.0)
        # second fetch walked the same frames at the same knob setting
        assert cam.shared_cache.hits > hits_before
        for da, db in zip(a, b):
            if da.frame is not None and db.frame is not None:
                np.testing.assert_array_equal(da.frame, db.frame)
                assert da.wire_bytes == db.wire_bytes
