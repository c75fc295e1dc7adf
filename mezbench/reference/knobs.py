"""Plain reference of Mez's five frame-quality knobs (paper Section 2.3.1).

Two transform paths, both straightforward NumPy:

* ``transform`` is the camera node's host pipeline that the serving path
  ships: knob4 artifact removal, then colorspace, area resize and box blur
  in floating point, rounded to uint8 after each stage.  ``wire_size`` is
  deflate level 1 of the payload, the bytes on the wire.
* ``exact_payload`` is the characterization grid's pipeline, stated in
  exact integer arithmetic: luma/chroma with integer coefficients, resize
  taps on the 2**-15 grid, box blur as integer sums, every rounding
  half-to-even on exact values.  Any correct implementation on any backend
  gives these bytes.

``transform(..., lower=True)`` computes in bfloat16 intermediates: the
control that a correct run must not be confused with.
"""

from __future__ import annotations

import itertools
import zlib

import numpy as np

RESOLUTION_SCALES = (1.0, 0.6833, 0.5, 0.3333, 0.25)
COLORSPACES = ("bgr", "gray", "yuv420")
BLUR_KERNELS = (0, 5, 8, 10, 15)
ARTIFACT_MODES = ("off", "movers", "contours")
DIFF_THRESHOLDS = (-1.0, 0.0, 0.01, 0.03, 0.06, 0.12)
ARTIFACT_THRESH = 18.0
PIXEL_DELTA = 8.0
RESIZE_BITS = 15

# every (resolution, colorspace, blur, artifact, diff) setting, in the
# program's grid order
GRID = tuple(itertools.product(
    range(len(RESOLUTION_SCALES)), range(len(COLORSPACES)),
    range(len(BLUR_KERNELS)), range(len(ARTIFACT_MODES)),
    range(len(DIFF_THRESHOLDS))))


def settings(include_artifact: bool) -> tuple[tuple[int, ...], ...]:
    return GRID if include_artifact else tuple(s for s in GRID if s[3] == 0)


def overhead_ms(s) -> float:
    """Modelled per-frame modification cost on the camera node (ms)."""
    res, cs, blur, art, diff = s
    cost = 1.0
    if RESOLUTION_SCALES[res] < 1.0:
        cost += 3.0
    if COLORSPACES[cs] != "bgr":
        cost += 2.0
    if BLUR_KERNELS[blur]:
        cost += 2.2 + 0.2 * BLUR_KERNELS[blur]
    if ARTIFACT_MODES[art] != "off":
        cost += 14.0
    if DIFF_THRESHOLDS[diff] >= 0.0:
        cost += 1.5
    return cost


def _lp(x, lower: bool):
    """float32, or bfloat16 rounding of it for the control."""
    if not lower:
        return x
    import ml_dtypes
    return np.asarray(x, np.float32).astype(ml_dtypes.bfloat16
                                            ).astype(np.float32)


# -- the serving path's host transform ----------------------------------------


def _resize(frame: np.ndarray, scale: float, lower: bool) -> np.ndarray:
    if scale >= 0.999:
        return frame
    h, w = frame.shape[:2]
    nh, nw = max(1, int(round(h * scale))), max(1, int(round(w * scale)))
    ys = np.clip((np.arange(nh) + 0.5) / scale - 0.5, 0, h - 1)
    xs = np.clip((np.arange(nw) + 0.5) / scale - 0.5, 0, w - 1)
    y0 = np.floor(ys).astype(np.int64)
    y1 = np.minimum(y0 + 1, h - 1)
    x0 = np.floor(xs).astype(np.int64)
    x1 = np.minimum(x0 + 1, w - 1)
    wy = (ys - y0)[:, None, None]
    wx = (xs - x0)[None, :, None]
    f = frame.astype(np.float32)
    if f.ndim == 2:
        f = f[..., None]
    top = _lp(f[y0][:, x0] * (1 - wx) + f[y0][:, x1] * wx, lower)
    bot = _lp(f[y1][:, x0] * (1 - wx) + f[y1][:, x1] * wx, lower)
    out = _lp(top * (1 - wy) + bot * wy, lower)
    out = np.clip(np.round(out), 0, 255).astype(np.uint8)
    return out if frame.ndim == 3 else out[..., 0]


def _colorspace(frame: np.ndarray, mode: str, lower: bool) -> np.ndarray:
    if mode == "bgr" or frame.ndim == 2:
        return frame
    f = frame.astype(np.float32)
    b, g, r = f[..., 0], f[..., 1], f[..., 2]
    y = _lp(0.114 * b + 0.587 * g + 0.299 * r, lower)
    if mode == "gray":
        return np.clip(np.round(y), 0, 255).astype(np.uint8)
    u = _lp(0.492 * (b - y) + 128.0, lower)
    v = _lp(0.877 * (r - y) + 128.0, lower)
    planes = [np.clip(np.round(p), 0, 255).astype(np.uint8)
              for p in (y, u[::2, ::2], v[::2, ::2])]
    h, w = planes[0].shape
    uh, uw = planes[1].shape
    pw = max(w, 2 * uw)
    top = np.zeros((h, pw), np.uint8)
    top[:, :w] = planes[0]
    bottom = np.zeros((uh, pw), np.uint8)
    bottom[:, :uw] = planes[1]
    bottom[:, uw:2 * uw] = planes[2]
    return np.concatenate([top, bottom], axis=0)


def _blur(frame: np.ndarray, k: int, lower: bool) -> np.ndarray:
    if k <= 1:
        return frame
    f = frame.astype(np.float32)
    squeeze = f.ndim == 2
    if squeeze:
        f = f[..., None]
    pad = k // 2
    fp = np.pad(f, ((pad, k - 1 - pad), (0, 0), (0, 0)), mode="edge")
    c = np.cumsum(fp, axis=0)
    c = np.concatenate([np.zeros((1,) + c.shape[1:], c.dtype), c], axis=0)
    f = _lp((c[k:] - c[:-k]) / k, lower)
    fp = np.pad(f, ((0, 0), (pad, k - 1 - pad), (0, 0)), mode="edge")
    c = np.cumsum(fp, axis=1)
    c = np.concatenate([np.zeros((c.shape[0], 1, c.shape[2]), c.dtype), c],
                       axis=1)
    f = _lp((c[:, k:] - c[:, :-k]) / k, lower)
    out = np.clip(np.round(f), 0, 255).astype(np.uint8)
    return out[..., 0] if squeeze else out


def _artifact_removal(frame, background, mode: str) -> np.ndarray:
    if mode == "off":
        return frame
    diff = np.abs(frame.astype(np.float32)
                  - background.astype(np.float32)).mean(axis=-1)
    mask = diff > ARTIFACT_THRESH
    m = mask.copy()
    m[1:, :] |= mask[:-1, :]
    m[:-1, :] |= mask[1:, :]
    m[:, 1:] |= mask[:, :-1]
    m[:, :-1] |= mask[:, 1:]
    if mode == "contours":
        er = m.copy()
        er[1:, :] &= m[:-1, :]
        er[:-1, :] &= m[1:, :]
        er[:, 1:] &= m[:, :-1]
        er[:, :-1] &= m[:, 1:]
        m = m & ~er
    out = frame.copy()
    out[~m] = 0
    return out


def transform(frame: np.ndarray, s, background: np.ndarray, *,
              lower: bool = False) -> np.ndarray:
    """The payload the camera ships for ``frame`` at setting ``s``."""
    res, cs, blur, art = s[:4]
    out = _artifact_removal(frame, background, ARTIFACT_MODES[art])
    out = _colorspace(out, COLORSPACES[cs], lower)
    out = _resize(out, RESOLUTION_SCALES[res], lower)
    return _blur(out, BLUR_KERNELS[blur], lower)


def wire_size(payload: np.ndarray) -> int:
    return len(zlib.compress(np.ascontiguousarray(payload).tobytes(), 1))


def change_fraction(frame: np.ndarray, last_sent: np.ndarray | None):
    """knob5's dissimilarity: share of pixels whose channel-mean absolute
    difference from the last sent frame exceeds 8 grey levels."""
    if last_sent is None or frame.shape != last_sent.shape:
        return None
    d = np.abs(frame.astype(np.float32) - last_sent.astype(np.float32))
    return float((d.mean(axis=-1) > PIXEL_DELTA).mean())


def proxy_features(payload: np.ndarray) -> np.ndarray:
    """The wire-size proxy's six byte-delta statistics of one payload:
    sum log2(1+|d|), zero count and |d| <= 2 count, horizontal then
    vertical, over every plane."""
    a = np.asarray(payload).astype(np.int64)
    a = a[None] if a.ndim == 2 else np.moveaxis(a, -1, 0)
    dx = np.abs(a[:, :, 1:] - a[:, :, :-1]).astype(np.float32)
    dy = np.abs(a[:, 1:, :] - a[:, :-1, :]).astype(np.float32)
    return np.asarray([
        np.log2(1.0 + dx).sum(), float((dx == 0).sum()),
        float((dx <= 2).sum()),
        np.log2(1.0 + dy).sum(), float((dy == 0).sum()),
        float((dy <= 2).sum())], np.float32)


# -- the characterization grid's exact pipeline ---------------------------------


def _resize_taps(n_in: int, n_out: int, scale: float) -> np.ndarray:
    """Integer bilinear taps [n_out, n_in] on the 2**-15 grid, each row
    summing to exactly 2**15 (the largest tap absorbs the rounding)."""
    one = 2 ** RESIZE_BITS
    if scale >= 0.999:
        return np.eye(n_in, dtype=np.int64) * one
    xs = np.clip((np.arange(n_out) + 0.5) / scale - 0.5, 0, n_in - 1)
    x0 = np.floor(xs).astype(np.int64)
    x1 = np.minimum(x0 + 1, n_in - 1)
    wx = (xs - x0).astype(np.float32)
    m = np.zeros((n_out, n_in), np.float32)
    np.add.at(m, (np.arange(n_out), x0), 1.0 - wx)
    np.add.at(m, (np.arange(n_out), x1), wx)
    q = np.rint(m.astype(np.float64) * one)
    q[np.arange(n_out), q.argmax(axis=1)] += one - q.sum(axis=1)
    return q.astype(np.int64)


def _blur_counts(n: int, k: int) -> np.ndarray:
    """Edge-clamped box taps as integer counts [n, n] (identity for k<=1)."""
    m = np.zeros((n, n), np.int64)
    if k <= 1:
        np.fill_diagonal(m, 1)
        return m
    rows = np.arange(n)
    for off in range(-(k // 2), k - k // 2):
        np.add.at(m, (rows, np.clip(rows + off, 0, n - 1)), 1)
    return m


def _round_div(num, den) -> np.ndarray:
    """Round half to even of ``num / den`` for integer-valued arrays below
    2**40 in magnitude and a positive integer ``den`` up to 10**6: the
    float64 quotient is exact on a tie and at least 1/den away from one
    otherwise, so ``rint`` decides every case exactly."""
    return np.rint(np.asarray(num, np.float64) / den)


def exact_geometry(h: int, w: int, res: int, cs: int) -> dict:
    """Output geometry and taps of one (resolution, colorspace) group."""
    scale = RESOLUTION_SCALES[res]
    ph = h + h // 2 if cs == 2 else h
    oh = max(1, int(round(ph * scale)))
    ow = max(1, int(round(w * scale)))
    return {"ry": _resize_taps(ph, oh, scale), "rx": _resize_taps(w, ow, scale),
            "out_h": oh, "out_w": ow, "planes": 3 if cs == 0 else 1}


def exact_planes(frames: np.ndarray, cs: int) -> np.ndarray:
    """uint8 [F, H, W, 3] -> int64 wire planes [F, P, packed_h, W]."""
    f = frames.astype(np.int64)
    b, g, r = f[..., 0], f[..., 1], f[..., 2]
    if cs == 0:
        return np.stack([b, g, r], axis=1)
    y = np.clip(_round_div(114 * b + 587 * g + 299 * r, 1000), 0, 255
                ).astype(np.int64)
    if cs == 1:
        return y[:, None]
    be, ge, re = b[:, ::2, ::2], g[:, ::2, ::2], r[:, ::2, ::2]
    y1000 = 114 * be + 587 * ge + 299 * re
    u = np.clip(_round_div(492 * (1000 * be - y1000), 10 ** 6) + 128, 0, 255
                ).astype(np.int64)
    v = np.clip(_round_div(877 * (1000 * re - y1000), 10 ** 6) + 128, 0, 255
                ).astype(np.int64)
    return np.concatenate([y, np.concatenate([u, v], axis=2)], axis=1)[:, None]


def artifact_keep(frames: np.ndarray, background: np.ndarray, mode: int
                  ) -> np.ndarray:
    """knob4 keep-masks [F, H, W] bool (mode 0 keeps everything)."""
    if mode == 0:
        return np.ones(frames.shape[:3], bool)
    d = np.abs(frames.astype(np.int64) - background.astype(np.int64)).sum(-1)
    mask = d > 3 * ARTIFACT_THRESH
    m = mask.copy()
    m[:, 1:, :] |= mask[:, :-1, :]
    m[:, :-1, :] |= mask[:, 1:, :]
    m[:, :, 1:] |= mask[:, :, :-1]
    m[:, :, :-1] |= mask[:, :, 1:]
    if mode == 1:
        return m
    er = m.copy()
    er[:, 1:, :] &= m[:, :-1, :]
    er[:, :-1, :] &= m[:, 1:, :]
    er[:, :, 1:] &= m[:, :, :-1]
    er[:, :, :-1] &= m[:, :, 1:]
    return m & ~er


def exact_resized(planes: np.ndarray, geo: dict) -> np.ndarray:
    """Exact resize of int planes [F, P, ph, W] -> [F, P, oh, ow] (integer
    values in float64).  Products of 2**15 taps and bytes stay below
    2**53, so float64 matmuls are exact, and the division by 2**30 is."""
    t = np.matmul(geo["ry"].astype(np.float64), planes.astype(np.float64))
    acc = np.matmul(t, geo["rx"].T.astype(np.float64))
    return np.clip(np.rint(acc / 2.0 ** (2 * RESIZE_BITS)), 0, 255)


def exact_blurred(rs: np.ndarray, k: int) -> np.ndarray:
    """Exact box blur of integer-valued [F, P, oh, ow] -> uint8."""
    if k <= 1:
        return rs.astype(np.uint8)
    oh, ow = rs.shape[-2:]
    by = _blur_counts(oh, k).astype(np.float64)
    bx = _blur_counts(ow, k).astype(np.float64)
    total = np.matmul(np.matmul(by, rs), bx.T)
    return np.clip(_round_div(total, k * k), 0, 255).astype(np.uint8)


def exact_payload(frames: np.ndarray, background: np.ndarray, res: int,
                  cs: int, blur: int, art: int, *,
                  enable: np.ndarray | None = None) -> np.ndarray:
    """Payloads uint8 [F, P, oh, ow] of one setting over a frame stack;
    ``enable`` (bool [F]) exempts frames from knob4."""
    keep = artifact_keep(frames, background, art)
    if enable is not None:
        keep = keep | ~enable[:, None, None]
    src = frames * keep[..., None]
    geo = exact_geometry(frames.shape[1], frames.shape[2], res, cs)
    rs = exact_resized(exact_planes(src, cs), geo)
    return exact_blurred(rs, BLUR_KERNELS[blur])
