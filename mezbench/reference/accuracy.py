"""The accuracy guarantee of what the subscriber received: the paper's
normalized F1 (Section 2.4) of the pedestrian detector on every delivered
frame against the generator's ground-truth boxes, over the same detector
on the original frames.  A frame dropped by knob5 counts its ground truth
as misses (at-most-once delivery).

The detector scores a payload as the characterization does (paper Section
4.3), so that the number is the one the controller's table promises: the
gray of the payload against the gray of the camera's background shipped
at the same setting (knob4 leaves the background whole), adaptive
threshold, cross dilation, 4-connected components boxed at half their
contrast peak, boxes scaled back to the camera's geometry.
"""

from __future__ import annotations

import numpy as np

from .. import detect as D
from . import char as RCH
from . import knobs as RK


def _gray(img: np.ndarray) -> np.ndarray:
    if img.ndim == 2:
        return img.astype(np.float32)
    f = img.astype(np.float32)
    return (np.float32(0.114) * f[..., 0] + np.float32(0.587) * f[..., 1]
            + np.float32(0.299) * f[..., 2])


def detect(payload: np.ndarray, background: np.ndarray, h: int, w: int
           ) -> np.ndarray:
    """Boxes [N, 4] (y0, x0, y1, x1) in an h x w camera's geometry;
    ``background`` is the camera's background at the payload's setting."""
    g, bg = _gray(payload), _gray(background)
    if bg.shape != g.shape:
        raise ValueError(f"payload {g.shape} against background {bg.shape}")
    diff = np.abs(g - bg)
    eff = float(D.adaptive_threshold(diff, RCH.DETECTOR_THRESH))
    sy, sx = h / g.shape[0], w / g.shape[1]
    return RCH._boxes(diff, eff, sy, sx, max(2.0, RCH.MIN_AREA / (sy * sx)))


def normalized_f1(rows) -> float | None:
    """``rows``: one per delivered frame, ``(gt_boxes, frame, payload,
    setting, background)``; ``payload`` None for a frame knob5 dropped,
    ``setting`` None for a frame shipped unmodified.  None when there is
    nothing to score."""
    bgs: dict = {}

    def background(bg, setting):
        key = (id(bg), None if setting is None else tuple(setting[:3]))
        if key not in bgs:
            bgs[key] = bg if setting is None else RK.transform(
                bg, tuple(setting[:3]) + (0,), bg)
        return bgs[key]

    got = np.zeros(3, np.int64)
    base = np.zeros(3, np.int64)
    for gt, frame, payload, setting, bg in rows:
        h, w = frame.shape[:2]
        base += D.match_f1(gt, detect(frame, bg, h, w))
        if payload is None:
            got[2] += len(gt)
        else:
            got += D.match_f1(gt, detect(payload, background(bg, setting),
                                         h, w))
    f1 = D.f1_from_counts(*base)
    if not rows or f1 <= 0:
        return None
    return D.f1_from_counts(*got) / f1
