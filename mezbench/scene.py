"""The load generator's cameras: synthetic pedestrian scenes with ground truth.

Each camera is a textured static background with moving rectangular
"pedestrians" (the scene model of the paper's JAAD/DukeMTMC stand-in),
Gaussian sensor noise, and per-frame ground-truth boxes.  Everything is
drawn from ``(seed, camera index)``, so one seed gives the same streams in
every run.  The benchmark owns this generator: the program under test only
ever receives the frames.

Frames are made in bulk before a run's window opens.  Sensor noise comes
from a small per-camera bank of noise fields, drawn per frame from the
camera's generator, so a stream of thousands of frames costs tens of
microseconds a frame instead of a fresh Gaussian field each.
"""

from __future__ import annotations

import dataclasses

import numpy as np

__all__ = ["DYNAMICS", "CameraStream", "ClipCamera", "make_streams"]

# name -> (objects min/max, box side min/max px, texture energy, speed px/frame)
DYNAMICS = {
    "simple": ((1, 2), (14, 26), 6.0, 1.5),
    "medium": ((3, 5), (12, 22), 12.0, 2.5),
    "complex": ((5, 8), (10, 20), 18.0, 3.5),
}
NOISE_SIGMA = 2.0
NOISE_BANK = 16


@dataclasses.dataclass
class CameraStream:
    """One camera's pre-made stream: ``frames[k]`` is published at tick k."""
    camera_id: str
    background: np.ndarray              # uint8 [H, W, 3]
    frames: list[np.ndarray]            # uint8 [H, W, 3] each
    boxes: list[np.ndarray]             # float32 [N, 4] (y0, x0, y1, x1) each

    def clip(self, n: int) -> list[tuple[float, np.ndarray, np.ndarray]]:
        """The first ``n`` frames as ``(timestamp, frame, boxes)`` rows."""
        return [(float(k), self.frames[k], self.boxes[k]) for k in range(n)]


class ClipCamera:
    """The camera ``characterize`` reads: a background and, from
    ``next_frame``, the first ``n`` frames of a stream."""

    def __init__(self, stream: CameraStream, n: int):
        self.background = stream.background
        self._rows = iter(stream.clip(n))

    def next_frame(self):
        return next(self._rows)


def _background(rng, h: int, w: int, texture: float) -> np.ndarray:
    yy, xx = np.mgrid[0:h, 0:w].astype(np.float32)
    bg = np.full((h, w), 90.0, np.float32)
    for _ in range(4):
        fy, fx = rng.uniform(0.005, 0.05, 2)
        ph = rng.uniform(0, 2 * np.pi)
        bg += texture * np.cos(2 * np.pi * (fy * yy + fx * xx) + ph)
    bg += rng.normal(0, texture * 0.3, bg.shape).astype(np.float32)
    bg = np.clip(bg, 0, 255)
    return np.stack([np.clip(bg * s, 0, 255) for s in (1.0, 0.96, 0.92)],
                    -1).astype(np.uint8)


def _camera(seed: int, index: int, camera_id: str, h: int, w: int,
            dynamics: str, n_frames: int) -> CameraStream:
    (nmin, nmax), (smin, smax), texture, speed = DYNAMICS[dynamics]
    rng = np.random.default_rng(np.random.SeedSequence([seed, index]))
    bg = _background(rng, h, w, texture)
    noise = rng.normal(0.0, NOISE_SIGMA, (NOISE_BANK, h, w, 3)
                       ).astype(np.float32)
    # background + noise, pre-clipped per bank entry; movers are painted
    # over it with their own noise below
    bg_noisy = np.clip(bg.astype(np.float32)[None] + noise, 0, 255
                       ).astype(np.uint8)
    n = int(rng.integers(nmin, nmax + 1))
    pos = rng.uniform([0, 0], [h - 1, w - 1], size=(n, 2))
    ang = rng.uniform(0, 2 * np.pi, size=n)
    vel = np.stack([np.sin(ang), np.cos(ang)], -1) * speed
    sizes = rng.integers(smin, smax + 1, size=(n, 2))
    sizes[:, 0] = (sizes[:, 0] * 1.8).astype(sizes.dtype)   # taller than wide
    shades = rng.integers(150, 255, size=(n, 3)).astype(np.float32)
    picks = rng.integers(0, NOISE_BANK, size=n_frames)
    frames, boxes = [], []
    for k in range(n_frames):
        pos += vel
        for d, lim in ((0, h - 1), (1, w - 1)):
            low, high = pos[:, d] < 0, pos[:, d] > lim
            vel[low | high, d] *= -1
            pos[low, d] *= -1
            pos[high, d] = 2 * lim - pos[high, d]
        b = int(picks[k])
        frame = bg_noisy[b].copy()
        fb = []
        for (py, px), (sy, sx), shade in zip(pos, sizes, shades):
            y0 = int(np.clip(py - sy / 2, 0, h - 1))
            y1 = int(np.clip(py + sy / 2, 1, h))
            x0 = int(np.clip(px - sx / 2, 0, w - 1))
            x1 = int(np.clip(px + sx / 2, 1, w))
            if y1 - y0 < 2 or x1 - x0 < 2:
                continue
            frame[y0:y1, x0:x1] = np.clip(
                shade + noise[b, y0:y1, x0:x1], 0, 255).astype(np.uint8)
            fb.append((y0, x0, y1, x1))
        frames.append(frame)
        boxes.append(np.asarray(fb, np.float32).reshape(-1, 4))
    return CameraStream(camera_id, bg, frames, boxes)


def make_streams(seed: int, config: dict, n_frames: int, *,
                 first_index: int = 0, count: int | None = None
                 ) -> list[CameraStream]:
    """``count`` cameras (default: the configuration's ``num_cameras``) of
    ``n_frames`` frames each, at the configuration's frame size and scene
    dynamics; camera i is drawn from ``(seed, first_index + i)``."""
    count = config["num_cameras"] if count is None else count
    h, w = config["frame_height"], config["frame_width"]
    return [_camera(seed, first_index + i, f"cam{first_index + i}", h, w,
                    config["dynamics"], n_frames) for i in range(count)]
