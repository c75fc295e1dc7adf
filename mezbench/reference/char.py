"""Plain reference of Mez's knob-grid characterization (paper Section 4.3).

For every (resolution, colorspace, blur, knob4 mode) combination over a
calibration clip: the exact payloads (``knobs.exact_payload``), the
wire-size proxy's byte-delta features, a deflate-calibrated linear size
proxy, the subscriber detector on each payload against the payload of the
background, F1 against ground truth with knob5's drop patterns, and the
controller's table of settings that keep at least ``min_accuracy`` of the
full-quality F1.

The sweep follows the batched engine's published recipe (one calibration
frame per combination deflated, the proxy fitted per colorspace and knob4
on/off, background and clip frames in one stack), written as plain NumPy.
"""

from __future__ import annotations

import dataclasses
import zlib

import numpy as np
from scipy import ndimage

from . import knobs as RK
from .. import detect as D

DETECTOR_THRESH = 28.0
MIN_AREA = 12.0


_LOG2 = np.log2(1.0 + np.arange(256, dtype=np.float64))


def planes_features(p: np.ndarray) -> np.ndarray:
    """Proxy features of payloads [..., P, oh, ow] -> [..., 6] (float64):
    per direction, sum log2(1+|d|), the zero count and the |d| <= 2 count,
    from a histogram of the byte deltas of each payload."""
    lead = p.shape[:-3]
    n = int(np.prod(lead)) if lead else 1
    a = p.reshape(n, -1, *p.shape[-2:]).astype(np.int16)
    out = np.zeros((n, 6))
    base = (np.arange(n, dtype=np.int64) * 256)[:, None]
    for j, d in enumerate((np.abs(a[..., :, 1:] - a[..., :, :-1]),
                           np.abs(a[..., 1:, :] - a[..., :-1, :]))):
        hist = np.bincount((base + d.reshape(n, -1)).ravel(),
                           minlength=n * 256).reshape(n, 256)
        out[:, 3 * j] = hist @ _LOG2
        out[:, 3 * j + 1] = hist[:, 0]
        out[:, 3 * j + 2] = hist[:, :3].sum(1)
    return out.reshape(*lead, 6)


def payload_gray(p: np.ndarray) -> np.ndarray:
    """The detector's gray plane of payloads [..., P, oh, ow]."""
    pf = p.astype(np.float32)
    if p.shape[-3] == 3:
        return (np.float32(0.114) * pf[..., 0, :, :]
                + np.float32(0.587) * pf[..., 1, :, :]
                + np.float32(0.299) * pf[..., 2, :, :])
    return pf[..., 0, :, :]


def _boxes(diff: np.ndarray, eff: float, sy: float, sx: float,
           min_px: float) -> np.ndarray:
    """Components of one thresholded, dilated diff image, boxed on their
    pixels at or above half the component's 95th-percentile contrast."""
    labels, n = ndimage.label(D.dilate_cross(diff > eff))
    out = []
    if n:
        flat = labels.ravel()
        fg = np.flatnonzero(flat)
        d = diff.ravel()[fg]
        order = np.lexsort((d, flat[fg]))
        lab, d, pix = flat[fg][order], d[order], fg[order]
        starts = np.flatnonzero(np.r_[True, lab[1:] != lab[:-1]])
        ends = np.append(starts[1:], lab.size)
        for s0, e0 in zip(starts, ends):
            m = e0 - s0
            if m < min_px:
                continue
            v = (m - 1) * 0.95
            lo = int(np.floor(v))
            a, b = d[s0 + lo], d[min(s0 + lo + 1, e0 - 1)]
            # float32 difference, float64 interpolation, as NumPy does for
            # a float32 array against a float64 fraction
            peak = np.float64(a) + np.float64(v - lo) * np.float64(b - a)
            seg = d[s0:e0]
            sel = seg >= 0.5 * peak
            if sel.sum() < 2:
                sel[:] = True
            ys, xs = np.divmod(pix[s0:e0][sel], diff.shape[1])
            out.append((ys.min() * sy, xs.min() * sx, (ys.max() + 1) * sy,
                        (xs.max() + 1) * sx))
    return np.asarray(out, np.float32).reshape(-1, 4)


def drop_pattern(counts: np.ndarray, pixels: int, thresh: float):
    n = counts.shape[0]
    drops = np.zeros(n, bool)
    if thresh < 0.0:
        return drops
    last = None
    for i in range(n):
        if last is not None and counts[i, last] / pixels <= thresh:
            drops[i] = True
        else:
            last = i
    return drops


@dataclasses.dataclass
class Characterization:
    kept: dict                 # setting tuple -> (size, accuracy)
    activity: float
    coeffs: np.ndarray         # [3, 2, 8] wire-size proxy per (cs, knob4)


def characterize(background: np.ndarray, clip: list, *,
                 include_artifact: bool, min_accuracy: float,
                 on_group=None) -> Characterization:
    """The table for ``clip`` (rows ``(ts, frame, gt_boxes)``).
    ``on_group(res, cs, payload, feats)``, when given, sees each
    (resolution, colorspace) group's payloads [S, F+1, P, oh, ow] and
    features [S, F+1, 6] over ``[background, *frames]``, S in the grid's
    (knob4, blur) order."""
    frames = [f for _, f, _ in clip]
    gts = [g for _, _, g in clip]
    n = len(frames)
    h, w = background.shape[:2]
    stack = np.stack([background] + frames)
    enable = np.r_[False, np.ones(n, bool)]
    arts = (0, 1, 2) if include_artifact else (0,)
    f32 = stack.astype(np.float32)
    counts = np.stack([
        ((np.abs(f32[1:] - f32[1 + i]).sum(-1) / np.float32(3.0))
         > RK.PIXEL_DELTA).sum((1, 2)) for i in range(n)], axis=1)
    counts = counts.astype(np.int64)           # counts[i, j]: frame i vs j

    match = {}       # combo -> [F, 3] (tp, fp, fn)
    feats_of, cal = {}, []
    for res in range(len(RK.RESOLUTION_SCALES)):
        for cs in range(len(RK.COLORSPACES)):
            geo = RK.exact_geometry(h, w, res, cs)
            n_set = len(arts) * len(RK.BLUR_KERNELS)
            pays = []
            for ai, art in enumerate(arts):
                keep = RK.artifact_keep(stack, background, art)
                keep |= ~enable[:, None, None]
                rs = RK.exact_resized(
                    RK.exact_planes(stack * keep[..., None], cs), geo)
                for bi, k in enumerate(RK.BLUR_KERNELS):
                    pays.append(RK.exact_blurred(rs, k))
            pay = np.stack(pays)                      # [S, n+1, P, oh, ow]
            feats = planes_features(pay)              # [S, n+1, 6]
            if on_group is not None:
                on_group(res, cs, pay, feats)
            gray = payload_gray(pay)
            diff = np.abs(gray[:, 1:] - gray[:, :1])  # [S, n, gh, gw]
            s_dim = diff.shape[0]
            eff = D.adaptive_threshold(diff.reshape(s_dim, n, -1),
                                       DETECTOR_THRESH, axis=-1)
            gh, gw = diff.shape[2:]
            sy, sx = h / gh, w / gw
            min_px = max(2.0, MIN_AREA / (sy * sx))
            for si in range(s_dim):
                art, b = arts[si // len(RK.BLUR_KERNELS)], \
                    si % len(RK.BLUR_KERNELS)
                combo = (res, cs, b, art)
                match[combo] = np.asarray([
                    D.match_f1(gts[fi], _boxes(diff[si, fi], eff[si, fi],
                                               sy, sx, min_px))
                    for fi in range(n)], np.int64)
                feats_of[combo] = feats[si, 1:]
                ci = 1 + (res * s_dim + si) % n
                p = pay[si, ci]
                wire = np.moveaxis(p, 0, -1) if cs == 0 else p[0]
                cal.append((cs, art, p.size, feats[si, ci],
                            len(zlib.compress(
                                np.ascontiguousarray(wire).tobytes(), 1))))

    coeffs = np.zeros((3, 2, 8))
    for cs in range(3):
        for a in range(2):
            rows = [r for r in cal if r[0] == cs and (r[1] > 0) == a]
            if rows:
                x = np.stack([np.concatenate([[r[2]], r[3], [1.0]])
                              for r in rows])
                y = np.asarray([r[4] for r in rows], np.float64)
                coeffs[cs, a] = np.linalg.lstsq(x, y, rcond=None)[0]
    sizes = {}
    for combo, fe in feats_of.items():
        res, cs, b, art = combo
        nb = RK.exact_geometry(h, w, res, cs)
        nbytes = nb["planes"] * nb["out_h"] * nb["out_w"]
        x = np.concatenate([np.full((n, 1), float(nbytes)), fe,
                            np.ones((n, 1))], axis=1)
        sizes[combo] = np.maximum(x @ coeffs[cs, int(art > 0)], 16.0)

    gt_sizes = np.asarray([len(g) for g in gts])
    base_f1 = D.f1_from_counts(*match[(0, 0, 0, 0)].sum(axis=0))
    drops = {di: drop_pattern(counts, h * w, t)
             for di, t in enumerate(RK.DIFF_THRESHOLDS)}
    kept = {}
    for s in RK.settings(include_artifact):
        combo, dr = s[:4], drops[s[4]]
        c = match[combo][~dr].sum(axis=0)
        f1 = D.f1_from_counts(int(c[0]), int(c[1]),
                              int(c[2] + gt_sizes[dr].sum()))
        acc = f1 / base_f1 if base_f1 > 0 else 0.0
        ks = sizes[combo][~dr]
        size = float(np.median(ks)) if ks.size else 0.0
        if acc >= min_accuracy and size > 0:
            kept[s] = (size, acc)
    activity = float(np.mean([counts[i, i - 1] / (h * w)
                              for i in range(1, n)]))
    return Characterization(kept, activity, coeffs)
