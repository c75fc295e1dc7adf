"""The subscriber detector's threshold and mask steps and the paper's F1
protocol, as the reference characterization scores with them.

A fixed copy kept with the benchmark, so the accuracy yardstick cannot move
when the program's own detector changes.  Scoring follows the paper
(Section 2.4): each ground-truth box is matched exclusively to its
highest-IoU detection, IoU > 0.5 is a true positive, F1 = 2PR/(P+R).
"""

from __future__ import annotations

import numpy as np

__all__ = ["adaptive_threshold", "dilate_cross", "match_f1",
           "f1_from_counts"]


def adaptive_threshold(diff: np.ndarray, thresh: float, axis=None):
    med, pct = np.percentile(diff, [50.0, 99.8], axis=axis)
    return np.maximum(3.0 * med + 4.0, np.minimum(thresh, 0.45 * pct))


def dilate_cross(mask: np.ndarray) -> np.ndarray:
    m = mask.copy()
    m[..., 1:, :] |= mask[..., :-1, :]
    m[..., :-1, :] |= mask[..., 1:, :]
    m[..., :, 1:] |= mask[..., :, :-1]
    m[..., :, :-1] |= mask[..., :, 1:]
    return m


def _iou(a: np.ndarray, b: np.ndarray) -> np.ndarray:
    if len(a) == 0 or len(b) == 0:
        return np.zeros((len(a), len(b)), np.float32)
    y0 = np.maximum(a[:, None, 0], b[None, :, 0])
    x0 = np.maximum(a[:, None, 1], b[None, :, 1])
    y1 = np.minimum(a[:, None, 2], b[None, :, 2])
    x1 = np.minimum(a[:, None, 3], b[None, :, 3])
    inter = np.clip(y1 - y0, 0, None) * np.clip(x1 - x0, 0, None)
    area_a = (a[:, 2] - a[:, 0]) * (a[:, 3] - a[:, 1])
    area_b = (b[:, 2] - b[:, 0]) * (b[:, 3] - b[:, 1])
    union = area_a[:, None] + area_b[None, :] - inter
    return np.where(union > 0, inter / union, 0.0).astype(np.float32)


def match_f1(gt: np.ndarray, det: np.ndarray, *, iou_thresh: float = 0.5
             ) -> tuple[int, int, int]:
    """Greedy exclusive matching, best IoU first: (TP, FP, FN)."""
    iou = _iou(gt, det)
    pairs = sorted(((iou[i, j], i, j) for i in range(len(gt))
                    for j in range(len(det))), reverse=True)
    used_gt, used_det = set(), set()
    for v, i, j in pairs:
        if v <= iou_thresh:
            break
        if i in used_gt or j in used_det:
            continue
        used_gt.add(i)
        used_det.add(j)
    return len(used_gt), len(det) - len(used_det), len(gt) - len(used_gt)


def f1_from_counts(tp: int, fp: int, fn: int) -> float:
    if tp == 0:
        return 0.0
    p, r = tp / (tp + fp), tp / (tp + fn)
    return 2 * p * r / (p + r)
