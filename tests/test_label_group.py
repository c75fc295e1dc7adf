"""The device labeler ``grid_engine._label_group`` against a plain
reference, bit for bit, and its round counter ``label_rounds``.

The reference is SciPy: the cross dilation of the thresholded diff,
``ndimage.label``'s 4-connected components, each component's pixels
carrying its minimum flat index and background the ``gh*gw`` sentinel.
Masks are handed in as ``diff`` 1.0 on, 0.0 off, with the threshold 0.5.
"""

import numpy as np
import pytest
from scipy import ndimage

from repro.core import grid_engine as GE

H, W = 144, 256


def _reference(diff: np.ndarray, eff: np.ndarray) -> np.ndarray:
    mask = diff > eff[:, :, None, None]
    cross = ndimage.generate_binary_structure(2, 1)
    gh, gw = mask.shape[2:]
    big = gh * gw
    flat = np.arange(big)
    out = np.empty(mask.shape, np.int32)
    for idx in np.ndindex(mask.shape[:2]):
        labels, n = ndimage.label(
            ndimage.binary_dilation(mask[idx], structure=cross), cross)
        low = np.full(n + 1, big, np.int64)
        np.minimum.at(low, labels.ravel(), flat)
        low[0] = big
        out[idx] = low[labels]
    return out


def _as_input(masks: np.ndarray):
    masks = np.asarray(masks, bool)
    return (masks.astype(np.float32),
            np.full(masks.shape[:2], 0.5, np.float32))


def _random(shape, density, seed):
    return np.random.default_rng(seed).random(shape) < density


def _blobs(shape, n, seed):
    """``n`` filled ellipses of 2-14 pixels' radius in every image."""
    rng = np.random.default_rng(seed)
    gh, gw = shape[-2:]
    yy, xx = np.mgrid[:gh, :gw]
    out = np.zeros(shape, bool)
    for idx in np.ndindex(shape[:-2]):
        for _ in range(n):
            cy, cx = rng.uniform(0, gh), rng.uniform(0, gw)
            ry, rx = rng.uniform(2, 14, size=2)
            out[idx] |= ((yy - cy) / ry) ** 2 + ((xx - cx) / rx) ** 2 <= 1
    return out


def _serpentine(gh, gw, turns):
    """A 1-pixel path of ``turns + 1`` rows 4 apart, joined at alternate
    ends: after the cross dilation, one 3-pixel-wide corridor."""
    m = np.zeros((gh, gw), bool)
    for k in range(turns + 1):
        m[1 + 4 * k, 1:gw - 1] = True
        if k < turns:
            m[1 + 4 * k:6 + 4 * k, gw - 2 if k % 2 == 0 else 1] = True
    return m


def _spiral(gh, gw):
    """A 1-pixel rectangular spiral inward from the corner, 4 pixels
    between its rings."""
    m = np.zeros((gh, gw), bool)
    top, bottom, left, right = 1, gh - 2, 1, gw - 2
    y, x = top, left
    while True:
        m[y, x:right + 1] = True
        x, top = right, top + 4
        if top > bottom:
            break
        m[y:bottom + 1, x] = True
        y, right = bottom, right - 4
        if left > right:
            break
        m[y, left:x + 1] = True
        x, bottom = left, bottom - 4
        if top > bottom:
            break
        m[top:y + 1, x] = True
        y, left = top, left + 4
        if left > right:
            break
    return m


def _single_pixels():
    m = np.zeros((H, W), bool)
    m[[0, 0, H - 1, H - 1, 70, 20], [0, W - 1, 0, W - 1, 128, 200]] = True
    return m


def _touch_borders():
    """One ring on every border with a cross through the middle, and one
    band from the top border to the bottom."""
    ring = np.zeros((H, W), bool)
    ring[[0, -1], :] = ring[:, [0, -1]] = True
    ring[H // 2, :] = ring[:, W // 2] = True
    band = np.zeros((H, W), bool)
    band[:, 40] = True
    return np.stack([ring, band])


CASES = {
    "random-1pct": lambda: _random((2, 3, H, W), 0.01, 1),
    "random-5pct": lambda: _random((2, 3, H, W), 0.05, 2),
    "random-30pct": lambda: _random((2, 3, H, W), 0.30, 3),
    "random-60pct": lambda: _random((2, 3, H, W), 0.60, 4),
    "all-background": lambda: np.zeros((1, 2, H, W), bool),
    "all-foreground": lambda: np.ones((1, 2, H, W), bool),
    "single-pixels": lambda: _single_pixels()[None, None],
    "touch-every-border": lambda: _touch_borders()[None],
    "serpentine": lambda: _serpentine(H, W, 35)[None, None],
    "spiral": lambda: _spiral(H, W)[None, None],
    # the cell's (res, cs) groups are [15 settings, 32 frames, gh, gw]
    "cell-group-144x256": lambda: _blobs((15, 2, H, W), 6, 5),
    "cell-group-216x256": lambda: _blobs((15, 2, 216, W), 6, 6)
    | _random((15, 2, 216, W), 0.01, 7),
}


@pytest.mark.parametrize("case", list(CASES))
def test_label_group_matches_scipy(case):
    diff, eff = _as_input(CASES[case]())
    got = np.asarray(GE._label_group(diff, eff))
    assert got.dtype == np.int32 and got.shape == diff.shape
    np.testing.assert_array_equal(got, _reference(diff, eff))


def test_label_rounds_is_one_on_an_empty_mask():
    assert GE.label_rounds(*_as_input(np.zeros((2, 3, 48, 64), bool))) == 1


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_label_rounds_few_on_blobs(seed):
    assert GE.label_rounds(*_as_input(_blobs((2, 4, H, W), 12, seed))) <= 6


def test_label_rounds_grow_with_serpentine_turns():
    rounds = [GE.label_rounds(*_as_input(_serpentine(H, W, t)[None, None]))
              for t in (1, 4, 12, 35)]
    assert rounds == sorted(set(rounds)), rounds
