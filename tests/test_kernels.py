"""Per-kernel allclose vs ref.py oracles, shape/dtype sweeps (interpret mode)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ref
from repro.kernels.decode_attention import decode_attention
from repro.kernels.flash_attention import flash_attention
from repro.kernels.frame_knobs import frame_knobs
from repro.kernels.linear_scan import wkv_linear_scan
from repro.kernels.quantize import dequantize_blocks, quantize_blocks
from repro.models.attention import repeat_kv

KEY = jax.random.PRNGKey(42)


def rand(i, shape, dtype=jnp.float32, scale=0.5):
    return (jax.random.normal(jax.random.fold_in(KEY, i), shape) * scale
            ).astype(dtype)


class TestQuantize:
    @pytest.mark.parametrize("shape", [(256, 512), (512, 1024), (256, 1536)])
    @pytest.mark.parametrize("bits", [8, 4])
    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    def test_matches_ref(self, shape, bits, dtype):
        x = rand(0, shape, dtype)
        q, s = quantize_blocks(x, bits=bits, interpret=True)
        qr, sr = ref.quantize_ref(x, bits=bits)
        np.testing.assert_allclose(np.asarray(s), np.asarray(sr), rtol=1e-6)
        # exact except at half-integer ties, where XLA's reciprocal-multiply
        # division may land one level away (bounded by 1 quantization step)
        d = np.abs(np.asarray(q, np.int32) - np.asarray(qr, np.int32))
        assert d.max() <= 1 and (d != 0).mean() < 0.01
        xd = dequantize_blocks(q, s, interpret=True)
        xdr = ref.dequantize_ref(qr, sr)
        step = np.repeat(np.repeat(np.asarray(sr), min(256, x.shape[0]), 0),
                         min(512, x.shape[1]), 1)
        assert np.abs(np.asarray(xd) - np.asarray(xdr)).max() <= step.max() + 1e-7

    def test_roundtrip_error_bound(self):
        """|dequant(x) - x| <= scale/2 per block (symmetric rounding)."""
        x = rand(1, (256, 512))
        q, s = quantize_blocks(x, interpret=True)
        xd = dequantize_blocks(q, s, interpret=True)
        err = jnp.abs(xd - x)
        bound = jnp.repeat(jnp.repeat(s, 256, 0), 512, 1) * 0.5 + 1e-7
        assert bool((err <= bound).all())

    def test_int4_levels(self):
        x = rand(2, (256, 512))
        q, _ = quantize_blocks(x, bits=4, interpret=True)
        assert int(jnp.abs(q).max()) <= 7


class TestFlashAttention:
    @pytest.mark.parametrize("s,qh,kh,d", [
        (256, 8, 8, 64),    # MHA
        (256, 8, 2, 64),    # GQA
        (320, 4, 1, 32),    # MQA, padded seq
        (128, 8, 8, 128),
    ])
    @pytest.mark.parametrize("causal", [True, False])
    def test_matches_ref(self, s, qh, kh, d, causal):
        q = rand(3, (2, s, qh, d))
        k = rand(4, (2, s, kh, d))
        v = rand(5, (2, s, kh, d))
        out = flash_attention(q, k, v, causal=causal, block_q=128,
                              block_k=128, interpret=True)
        exp = ref.flash_attention_ref(q, repeat_kv(k, qh), repeat_kv(v, qh),
                                      causal=causal)
        np.testing.assert_allclose(np.asarray(out), np.asarray(exp),
                                   rtol=3e-5, atol=3e-5)

    def test_bf16(self):
        q = rand(6, (1, 128, 4, 64), jnp.bfloat16)
        k = rand(7, (1, 128, 4, 64), jnp.bfloat16)
        v = rand(8, (1, 128, 4, 64), jnp.bfloat16)
        out = flash_attention(q, k, v, block_q=64, block_k=64, interpret=True)
        exp = ref.flash_attention_ref(q, k, v)
        np.testing.assert_allclose(np.asarray(out, np.float32),
                                   np.asarray(exp, np.float32),
                                   rtol=2e-2, atol=2e-2)


class TestDecodeAttention:
    @pytest.mark.parametrize("smax,qh,kh,d,length", [
        (512, 8, 8, 64, 512), (512, 8, 2, 64, 300), (1024, 4, 1, 128, 7),
    ])
    def test_matches_ref(self, smax, qh, kh, d, length):
        q = rand(9, (2, 1, qh, d))
        kc = rand(10, (2, smax, kh, d))
        vc = rand(11, (2, smax, kh, d))
        ln = jnp.asarray(length, jnp.int32)
        out = decode_attention(q, kc, vc, ln, block_k=128, interpret=True)
        exp = ref.decode_attention_ref(q, repeat_kv(kc, qh),
                                       repeat_kv(vc, qh), ln)
        np.testing.assert_allclose(np.asarray(out), np.asarray(exp),
                                   rtol=3e-5, atol=3e-5)


class TestLinearScan:
    @pytest.mark.parametrize("s,h,kd,bt", [(64, 2, 16, 16), (128, 3, 32, 32),
                                           (96, 1, 64, 96)])
    def test_matches_ref(self, s, h, kd, bt):
        r = rand(12, (2, s, h, kd))
        k = rand(13, (2, s, h, kd))
        v = rand(14, (2, s, h, kd))
        logw = -jnp.exp(rand(15, (2, s, h, kd)) - 2.0)
        u = rand(16, (h, kd))
        y, st = wkv_linear_scan(r, k, v, logw, u, block_t=bt, interpret=True)
        yr, sr = ref.wkv_ref(r, k, v, logw, u)
        np.testing.assert_allclose(np.asarray(y), np.asarray(yr),
                                   rtol=3e-4, atol=3e-4)
        np.testing.assert_allclose(np.asarray(st), np.asarray(sr),
                                   rtol=3e-4, atol=3e-4)

    def test_state_carry_composes(self):
        """Running two halves with carried state == one full run."""
        r = rand(17, (1, 64, 2, 16)); k = rand(18, (1, 64, 2, 16))
        v = rand(19, (1, 64, 2, 16))
        logw = -jnp.exp(rand(20, (1, 64, 2, 16)) - 2.0)
        u = rand(21, (2, 16))
        y_full, st_full = ref.wkv_ref(r, k, v, logw, u)
        y1, st1 = ref.wkv_ref(r[:, :32], k[:, :32], v[:, :32], logw[:, :32], u)
        y2, st2 = ref.wkv_ref(r[:, 32:], k[:, 32:], v[:, 32:], logw[:, 32:],
                              u, state0=st1)
        np.testing.assert_allclose(np.asarray(st2), np.asarray(st_full),
                                   rtol=1e-5, atol=1e-6)
        np.testing.assert_allclose(np.asarray(y2), np.asarray(y_full[:, 32:]),
                                   rtol=1e-5, atol=1e-6)


class TestFrameKnobs:
    @pytest.mark.parametrize("h,w,blur", [(64, 128, 5), (48, 96, 3),
                                          (64, 128, 1)])
    def test_matches_ref(self, h, w, blur):
        f = (rand(22, (3, h, w), scale=60.0) + 128).clip(0, 255)
        p = (rand(23, (3, h, w), scale=60.0) + 128).clip(0, 255)
        out, ch = frame_knobs(f, p, blur_k=blur, interpret=True)
        outr, chr_ = ref.frame_knobs_ref(f, p, blur_k=blur)
        np.testing.assert_allclose(np.asarray(out), np.asarray(outr),
                                   rtol=1e-5, atol=1e-3)
        np.testing.assert_allclose(np.asarray(ch), np.asarray(chr_),
                                   rtol=1e-6, atol=1e-7)

    def test_change_metric_detects_motion(self):
        base = jnp.full((1, 32, 64), 100.0)
        moved = base.at[0, 8:16, 20:40].set(200.0)
        _, ch_same = frame_knobs(base, base, interpret=True)
        _, ch_moved = frame_knobs(moved, base, interpret=True)
        assert float(ch_same[0]) == 0.0
        np.testing.assert_allclose(float(ch_moved[0]), (8 * 20) / (32 * 64),
                                   rtol=1e-6)


class TestFrameKnobGrid:
    """Oracle sweep for the generalized grid kernel: every (resolution,
    colorspace) plan, all blur widths batched, interpret mode vs
    ``ref.frame_knob_grid_ref`` (bit-exact) and vs the float64 NumPy host
    pipeline ``knobs.transform_frame`` (within one grey level)."""

    H, W, F = 32, 48, 2

    @pytest.fixture(scope="class")
    def clip(self):
        rng = np.random.default_rng(11)
        base = rng.integers(40, 200, (self.H, self.W, 3))
        frames = np.clip(base[None] + rng.normal(0, 12, (self.F, self.H,
                                                         self.W, 3)),
                         0, 255).astype(np.uint8)
        prev = np.concatenate([frames[:1], frames[:-1]])
        return frames, prev

    @pytest.mark.parametrize("res", range(5))
    @pytest.mark.parametrize("cs", range(3))
    def test_matches_ref_and_numpy(self, clip, res, cs):
        from repro.core import knobs as K
        from repro.kernels.frame_knobs import build_transform_plan, \
            frame_knob_grid

        frames, prev = clip
        plan = build_transform_plan(
            self.H, self.W, scale=K.RESOLUTION_SCALES[res], cs=cs,
            blur_ks=K.BLUR_KERNELS)
        pk, fk, ck = frame_knob_grid(jnp.asarray(frames), jnp.asarray(prev),
                                     plan, interpret=True)
        pr, fr, cr = ref.frame_knob_grid_ref(jnp.asarray(frames),
                                             jnp.asarray(prev), plan)
        # bit-exact against the oracle
        np.testing.assert_array_equal(np.asarray(pk), np.asarray(pr))
        np.testing.assert_array_equal(np.asarray(fk), np.asarray(fr))
        np.testing.assert_array_equal(np.asarray(ck), np.asarray(cr))
        # one grey level of the float64 host pipeline (f32 vs f64 rounding)
        for b in range(len(K.BLUR_KERNELS)):
            for fi in range(self.F):
                want = K.transform_frame(frames[fi], K.KnobSetting(res, cs, b))
                got = np.asarray(pk)[b, fi]
                got = np.moveaxis(got, 0, -1) if cs == 0 else got[0]
                assert got.shape == want.shape
                d = np.abs(got.astype(np.int32) - want.astype(np.int32))
                assert d.max() <= 1
                assert (d != 0).mean() < 0.01

    def test_change_metric_matches_frame_difference(self, clip):
        from repro.core import knobs as K
        from repro.kernels.frame_knobs import build_transform_plan, \
            frame_knob_grid

        frames, prev = clip
        plan = build_transform_plan(self.H, self.W, scale=1.0, cs=1,
                                    blur_ks=(0,))
        _, _, ch = frame_knob_grid(jnp.asarray(frames), jnp.asarray(prev),
                                   plan, interpret=True)
        # knob5 semantics: the kernel's fraction drives the same drop
        # decision as the host frame_difference at every threshold
        for fi in range(1, self.F):
            frac = float(np.asarray(ch)[0, fi])
            for thresh in K.DIFF_THRESHOLDS:
                want = K.frame_difference(frames[fi], prev[fi], thresh)
                got = thresh >= 0.0 and frac <= thresh
                assert got == want


class TestFrameKnobGridExactRounding:
    """The grid kernel's arithmetic is exact, so its rounding matches exact
    rational rounding on every backend: checked here against Python
    integers and float64 (exact at these magnitudes), ties included."""

    def test_round_div_is_half_even(self):
        from repro.kernels.frame_knobs import _round_div

        nums = np.arange(-3000, 3001, dtype=np.int64) * 37
        for den in (2, 4, 25, 100, 225, 1000):
            got = np.asarray(_round_div(jnp.asarray(nums, jnp.int32), den))
            q, r = np.divmod(nums, den)             # floor division
            up = (2 * r > den) | ((2 * r == den) & (q % 2 == 1))
            np.testing.assert_array_equal(got, q + up)

    @pytest.mark.parametrize("res", range(5))
    def test_resize_matches_exact_rounding(self, res):
        from repro.core import knobs as K
        from repro.kernels.frame_knobs import (_resize, build_transform_plan,
                                               exact_operators)

        plan = build_transform_plan(40, 56, scale=K.RESOLUTION_SCALES[res],
                                    cs=0, blur_ks=(0,))
        ry, rx, _, _ = exact_operators(plan)
        rng = np.random.default_rng(res)
        plane = rng.integers(0, 255, (40, 56)).astype(np.float64)
        plane[:, 1::2] = plane[:, ::2] + 1      # neighbours 1 apart: ties
        want = np.round((ry.astype(np.float64) @ plane)
                        @ rx.astype(np.float64).T)
        got = np.asarray(_resize(jnp.asarray(plane, jnp.float32),
                                 jnp.asarray(ry), jnp.asarray(rx)))
        np.testing.assert_array_equal(got, want)


class TestFrameKnobGridArtifact:
    """knob4 (artifact removal / background subtraction) as a device-side
    per-setting operator: interpret-mode kernel vs ``frame_knob_grid_ref``
    (bit-exact) and vs the host ``knobs.apply_knobs`` pipeline (within one
    grey level), with the per-frame enable gating the characterization
    engine relies on."""

    H, W, F = 32, 48, 3

    @pytest.fixture(scope="class")
    def scene(self):
        rng = np.random.default_rng(23)
        base = rng.integers(40, 200, (self.H, self.W, 3))
        bg = np.clip(base + rng.normal(0, 2, base.shape), 0,
                     255).astype(np.uint8)
        frames = np.clip(base[None] + rng.normal(0, 10, (self.F, self.H,
                                                         self.W, 3)),
                         0, 255).astype(np.uint8)
        frames[1, 8:16, 10:22] = 245            # a bright mover
        frames[2, 20:28, 30:42] = 8             # a dark mover
        prev = np.concatenate([frames[:1], frames[:-1]])
        return frames, prev, bg

    @pytest.mark.parametrize("res,cs", [(0, 0), (2, 1), (1, 2), (4, 0)])
    def test_matches_ref_and_numpy(self, scene, res, cs):
        from repro.core import knobs as K
        from repro.kernels.frame_knobs import build_transform_plan, \
            frame_knob_grid

        frames, prev, bg = scene
        plan = build_transform_plan(
            self.H, self.W, scale=K.RESOLUTION_SCALES[res], cs=cs,
            blur_ks=(0, 5, 10), art_modes=(0, 1, 2))
        pk, fk, ck = frame_knob_grid(jnp.asarray(frames), jnp.asarray(prev),
                                     plan, background=jnp.asarray(bg),
                                     interpret=True)
        pr, fr, cr = ref.frame_knob_grid_ref(
            jnp.asarray(frames), jnp.asarray(prev), plan,
            background=jnp.asarray(bg))
        np.testing.assert_array_equal(np.asarray(pk), np.asarray(pr))
        np.testing.assert_array_equal(np.asarray(fk), np.asarray(fr))
        np.testing.assert_array_equal(np.asarray(ck), np.asarray(cr))
        # vs the host pipeline: artifact removal then transform, one grey
        n_blur = 3
        for a in range(3):
            for b in range(n_blur):
                si = a * n_blur + b
                for fi in range(self.F):
                    s = K.KnobSetting(res, cs, [0, 1, 3][b], a, 0)
                    r = K.apply_knobs(frames[fi], s, background=bg,
                                      last_sent=None)
                    got = np.asarray(pk)[si, fi]
                    got = np.moveaxis(got, 0, -1) if cs == 0 else got[0]
                    assert got.shape == r.frame.shape
                    d = np.abs(got.astype(np.int32)
                               - r.frame.astype(np.int32))
                    assert d.max() <= 1
                    assert (d != 0).mean() < 0.02

    def test_enable_gates_artifact_per_frame(self, scene):
        from repro.core import knobs as K
        from repro.kernels.frame_knobs import build_transform_plan, \
            frame_knob_grid

        frames, prev, bg = scene
        plan = build_transform_plan(self.H, self.W, scale=1.0, cs=1,
                                    blur_ks=(0,), art_modes=(1,))
        enable = np.asarray([0, 1, 1], np.int32)
        pk, _, _ = frame_knob_grid(jnp.asarray(frames), jnp.asarray(prev),
                                   plan, background=jnp.asarray(bg),
                                   art_enable=jnp.asarray(enable),
                                   interpret=True)
        # frame 0: knob4 disabled -> plain transform of the raw frame
        want = K.transform_frame(frames[0], K.KnobSetting(0, 1, 0))
        d = np.abs(np.asarray(pk)[0, 0, 0].astype(np.int32)
                   - want.astype(np.int32))
        assert d.max() <= 1
        # frames 1/2: knob4 live -> static background zeroed
        assert (np.asarray(pk)[0, 1] == 0).mean() > 0.5

    def test_artifact_plan_requires_background(self, scene):
        from repro.kernels.frame_knobs import build_transform_plan, \
            frame_knob_grid

        frames, prev, _ = scene
        plan = build_transform_plan(self.H, self.W, scale=1.0, cs=0,
                                    blur_ks=(0,), art_modes=(0, 1))
        with pytest.raises(ValueError, match="background"):
            frame_knob_grid(jnp.asarray(frames), jnp.asarray(prev), plan,
                            interpret=True)
