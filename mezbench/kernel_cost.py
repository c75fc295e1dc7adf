"""Bytes and operations that one ``frame_knob_grid`` call needs, from its
shapes alone: what the algorithm must read, write and compute, never what
an implementation happens to move.

One call evaluates one (resolution, colorspace) group: ``S`` settings
(knob4 mode x blur width) over ``F`` frames of ``H x W x 3`` bytes.

Bytes: the background and clip frames in once (the previous-frame operand
is the same clip shifted by one), the payloads out
(``S * F * P * oh * ow`` bytes) and, per (setting, frame), the six proxy
features and the change count as 4-byte values.

Operations, counted once where the algorithm needs them once:
  per frame                knob5 change metric (10 ops a pixel)
  per frame, knob4 mode    the keep mask (20 a pixel, modes 1-2), the
                           colorspace (gray 7, packed 4:2:0 9 a pixel),
                           separable bilinear resize (3 per tap pass and
                           output, both axes) and its rounding
  per setting and frame    separable box blur by running sums (8 an output
                           value) and its rounding (2), the proxy features
                           (24 an output value)
"""

from __future__ import annotations


def group_cost(h: int, w: int, out_h: int, out_w: int, planes: int,
               packed_h: int, n_frames: int, n_blur: int, art_modes: int
               ) -> tuple[float, float]:
    """(bytes, ops) of one grid call."""
    s = n_blur * art_modes
    pix = h * w
    out = planes * out_h * out_w
    nbytes = n_frames * pix * 3 + s * n_frames * (out + 7 * 4)
    color = {3: 0, 1: 7}.get(planes, 0) if packed_h == h else 9
    resize = planes * 3 * (out_h * w + out_h * out_w) + 2 * out
    per_mode = color * pix + resize
    mask = 20 * pix * (art_modes - 1)
    ops = n_frames * (10 * pix + mask + art_modes * per_mode
                      + s * (10 * out + 24 * out))
    return float(nbytes), float(ops)


def sweep_min_seconds(plans, h: int, w: int, n_frames: int, n_blur: int,
                      art_modes: int, peak: dict) -> tuple[float, str]:
    """Least time the chip could take for one sweep's grid calls, and the
    bound that sets it (``memory`` or ``compute``)."""
    t_mem = t_ops = 0.0
    for _, cs, geo in plans:
        packed = h + h // 2 if cs == 2 else h
        b, o = group_cost(h, w, geo["out_h"], geo["out_w"], geo["planes"],
                          packed, n_frames, n_blur, art_modes)
        t_mem += b / peak["hbm_bytes_per_s"]
        t_ops += o / peak["bf16_flops_per_s"]
    return (t_mem, "memory") if t_mem >= t_ops else (t_ops, "compute")
