"""Fused frame-quality kernels: the knob grid as device-resident compute.

The paper measures knob processing at ~10 ms/frame on the camera node's ARM
CPU -- 20.5% of end-to-end latency (Fig. 16) -- and proposes offload as
future work.  Two kernels implement that offload, TPU-native:

``frame_knobs``      the original fixed-function kernel (2x2 mean pool +
                     box blur + knob5 change metric on gray planes), kept
                     for the streaming hot path and back-compat.

``frame_knob_grid``  the generalized characterization kernel: ONE pass over
                     a clip evaluates a whole batch of knob settings.  Per
                     (setting, frame) grid program it applies

  1. knob4 artifact removal: background subtraction against a per-call
     background frame (channel-mean |f - bg| > 18, cross dilation, keep
     movers or just their contours, zero the rest) -- the per-setting mode
     id selects off/movers/contours, and a per-frame enable flag lets the
     caller exempt the background/padding frames, so knob4 characterization
     no longer falls back to the minutes-long NumPy path,
  2. knob2 colorspace: BGR planes / gray / packed 4:2:0 YUV (Y on top,
     U|V below -- the exact wire layout of ``knobs._to_colorspace``),
  3. knob1 resolution: arbitrary-factor bilinear resize expressed as a pair
     of per-axis operator matrices (``Ry @ plane @ Rx^T``) so any
     ``RESOLUTION_SCALES`` entry runs on the MXU -- the old kernel's 2x2
     mean pool is the special case ``scale=0.5``,
  4. knob3 blur: every ``BLUR_KERNELS`` width as per-setting edge-clamped
     band matrices (``By[s] @ img @ Bx[s]^T``),
  5. knob5 change metric: fraction of pixels changed vs. the previous
     frame (``|f - prev| > pixel_delta`` after channel-mean),
  6. wire-size proxy features: per-payload horizontal/vertical byte-delta
     statistics (sum of log2(1+|d|), zero-delta count, |d|<=2 count) that
     ``core.grid_engine`` calibrates against zlib level-1 -- so deflate
     never runs on the characterization hot path.

Rounding matches the host pipeline stage for stage (uint8 round/clip after
colorspace, after resize, after blur), and every stage is computed exactly:
integer colorspace and blur arithmetic, resize taps on a 2**-15 grid whose
products are split into exact limbs (``exact_operators``), half-even
rounding decided on exact values.  No result depends on a backend's
summation order or division, so the kernel on a TPU is bit-exact against
``repro.kernels.ref.frame_knob_grid_ref`` on a CPU, and within one grey
level of the float64 NumPy path in ``knobs.transform_frame``.

Geometry (colorspace mode, output height/width) is static per call; the
settings batch dimension carries the per-setting blur operators, so one
``pallas_call`` evaluates ``[n_settings, n_frames]`` programs in a single
HBM pass over the clip.  ``core.grid_engine`` groups the full knob grid by
(resolution, colorspace) and issues one call per group.

TPU layout: frames enter planes-first (``[3, H, W]`` blocks, W on the
lanes), the knob4 ints ride as scalar prefetch, and each program writes
its features and change count as one lane-dense ``[1, 128]`` stats row.
Every contraction is a 2-D f32 matmul at HIGHEST precision; subsampling,
packing, dilation and byte deltas are matmuls against 0/1 or +-1
operators, so the body needs no strided or unaligned slices.
"""

# mezlint: ref-parity: repro.kernels.ref.frame_knobs_ref
# mezlint: ref-parity: repro.kernels.ref.frame_knob_grid_ref

from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["frame_knobs", "TransformPlan", "build_transform_plan",
           "frame_knob_grid", "exact_operators", "resize_operator",
           "blur_operator",
           "proxy_features", "proxy_features_host", "N_PROXY_FEATURES",
           "ARTIFACT_THRESH"]

N_PROXY_FEATURES = 6   # (log2-sum, zero-count, <=2-count) x (dx, dy)
ARTIFACT_THRESH = 18.0  # knobs._artifact_removal's default mask threshold


# =============================================================================
# Original fixed-function kernel (unchanged semantics, back-compat)
# =============================================================================


def _knobs_kernel(f_ref, p_ref, o_ref, c_ref, *, blur_k: int,
                  pixel_delta: float):
    f = f_ref[0].astype(jnp.float32)                   # [H, W]
    prev = p_ref[0].astype(jnp.float32)
    h, w = f.shape

    # knob5 change metric
    changed = (jnp.abs(f - prev) > pixel_delta).astype(jnp.float32)
    c_ref[0] = changed.sum() / (h * w)

    # knob1: 2x2 mean pool
    pooled = f.reshape(h // 2, 2, w // 2, 2).mean(axis=(1, 3))

    # knob3: separable box blur with edge clamp (block-local)
    if blur_k > 1:
        pad = blur_k // 2
        acc = jnp.zeros_like(pooled)
        for dy in range(-pad, blur_k - pad):
            idx = jnp.clip(jnp.arange(h // 2) + dy, 0, h // 2 - 1)
            acc = acc + pooled[idx]
        pooled = acc / blur_k
        acc = jnp.zeros_like(pooled)
        for dx in range(-pad, blur_k - pad):
            idx = jnp.clip(jnp.arange(w // 2) + dx, 0, w // 2 - 1)
            acc = acc + pooled[:, idx]
        pooled = acc / blur_k

    o_ref[0] = pooled.astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("blur_k", "pixel_delta",
                                             "interpret"))
def frame_knobs(frames: jax.Array, prev: jax.Array, *, blur_k: int = 5,
                pixel_delta: float = 8.0, interpret: bool = False
                ) -> tuple[jax.Array, jax.Array]:
    """frames/prev: [N, H, W] (uint8 or float) -> (out [N, H/2, W/2] f32,
    changed_frac [N] f32)."""
    n, h, w = frames.shape
    assert h % 2 == 0 and w % 2 == 0, (h, w)
    return pl.pallas_call(
        functools.partial(_knobs_kernel, blur_k=blur_k,
                          pixel_delta=pixel_delta),
        grid=(n,),
        in_specs=[pl.BlockSpec((1, h, w), lambda i: (i, 0, 0)),
                  pl.BlockSpec((1, h, w), lambda i: (i, 0, 0))],
        out_specs=[pl.BlockSpec((1, h // 2, w // 2), lambda i: (i, 0, 0)),
                   pl.BlockSpec((1,), lambda i: (i,))],
        out_shape=[jax.ShapeDtypeStruct((n, h // 2, w // 2), jnp.float32),
                   jax.ShapeDtypeStruct((n,), jnp.float32)],
        interpret=interpret,
    )(frames, prev)


# =============================================================================
# Generalized knob-grid kernel
# =============================================================================

# Colorspace ids (static per call; match knobs.COLORSPACES order).
CS_BGR, CS_GRAY, CS_YUV420 = 0, 1, 2


def resize_operator(n_in: int, n_out: int, scale: float) -> np.ndarray:
    """One axis of ``knobs._resize_area`` as an [n_out, n_in] f32 operator.

    Row i carries the two bilinear taps of output sample i (edge-clamped,
    half-pixel-centre aligned).  ``scale >= 0.999`` yields the identity, so
    the full-resolution setting is exact pass-through.
    """
    if scale >= 0.999:
        return np.eye(n_in, dtype=np.float32)
    xs = np.clip((np.arange(n_out) + 0.5) / scale - 0.5, 0, n_in - 1)
    x0 = np.floor(xs).astype(np.int64)
    x1 = np.minimum(x0 + 1, n_in - 1)
    wx = (xs - x0).astype(np.float32)
    m = np.zeros((n_out, n_in), np.float32)
    np.add.at(m, (np.arange(n_out), x0), 1.0 - wx)
    np.add.at(m, (np.arange(n_out), x1), wx)
    return m


def blur_operator(n: int, k: int) -> np.ndarray:
    """``knobs._box_blur`` along one axis as an [n, n] edge-clamped band
    matrix (identity for k <= 1)."""
    m = np.zeros((n, n), np.float32)
    if k <= 1:
        np.fill_diagonal(m, 1.0)
        return m
    pad = k // 2
    rows = np.arange(n)
    for off in range(-pad, k - pad):
        np.add.at(m, (rows, np.clip(rows + off, 0, n - 1)),
                  np.float32(1.0 / k))
    return m


@dataclasses.dataclass(frozen=True)
class TransformPlan:
    """Device-ready operators for one (resolution, colorspace) group of the
    knob grid, batching every (artifact mode, blur width) pair of that group.

    The settings axis is artifact-major: setting ``a * len(blur_ks) + b``
    pairs artifact mode ``art_modes[a]`` with blur width ``blur_ks[b]``
    (``art_ids``/``blur_ids`` carry the per-setting values).  The plan fully
    determines output geometry, so one ``pallas_call`` (or its XLA twin in
    ``ref``) covers ``n_settings`` settings per frame.
    """
    cs: int                    # CS_BGR / CS_GRAY / CS_YUV420
    scale: float
    blur_ks: tuple[int, ...]
    art_modes: tuple[int, ...]  # knob4 modes batched (0=off, 1=movers, 2=contours)
    in_h: int                  # camera frame height
    in_w: int
    packed_h: int              # post-colorspace height (h + h//2 for yuv420)
    out_h: int                 # payload height after resize
    out_w: int
    n_planes: int              # 3 for bgr, 1 otherwise
    ry: np.ndarray             # [out_h, packed_h]
    rx: np.ndarray             # [out_w, in_w]
    bys: np.ndarray            # [S, out_h, out_h]
    bxs: np.ndarray            # [S, out_w, out_w]
    art_ids: np.ndarray        # [S] i32, per-setting artifact mode
    blur_ids: np.ndarray       # [S] i32, per-setting blur width

    @property
    def n_settings(self) -> int:
        return len(self.blur_ks) * len(self.art_modes)

    @property
    def with_artifact(self) -> bool:
        return bool((self.art_ids != 0).any())

    @property
    def payload_bytes(self) -> int:
        return self.n_planes * self.out_h * self.out_w


def build_transform_plan(h: int, w: int, *, scale: float, cs: int,
                         blur_ks: tuple[int, ...],
                         art_modes: tuple[int, ...] = (0,)) -> TransformPlan:
    """Build the operator bundle for one (resolution, colorspace) group.

    Requires even ``h``/``w`` for yuv420 (4:2:0 subsampling); the host
    NumPy path stays the oracle for odd geometries.
    """
    if cs == CS_YUV420 and (h % 2 or w % 2):
        raise ValueError(f"yuv420 grid transform needs even dims, got {h}x{w}")
    packed_h = h + h // 2 if cs == CS_YUV420 else h
    ry = resize_operator(packed_h, max(1, int(round(packed_h * scale))), scale)
    rx = resize_operator(w, max(1, int(round(w * scale))), scale)
    out_h, out_w = ry.shape[0], rx.shape[0]
    by_of = {k: blur_operator(out_h, k) for k in blur_ks}
    bx_of = {k: blur_operator(out_w, k) for k in blur_ks}
    pairs = [(a, k) for a in art_modes for k in blur_ks]   # artifact-major
    bys = np.stack([by_of[k] for _, k in pairs])
    bxs = np.stack([bx_of[k] for _, k in pairs])
    art_ids = np.asarray([a for a, _ in pairs], np.int32)
    blur_ids = np.asarray([k for _, k in pairs], np.int32)
    return TransformPlan(cs=cs, scale=scale, blur_ks=tuple(blur_ks),
                         art_modes=tuple(art_modes),
                         in_h=h, in_w=w, packed_h=packed_h,
                         out_h=out_h, out_w=out_w,
                         n_planes=3 if cs == CS_BGR else 1,
                         ry=ry, rx=rx, bys=bys, bxs=bxs,
                         art_ids=art_ids, blur_ids=blur_ids)


def _to_planes(frame: jax.Array, cs: int) -> jax.Array:
    """uint8 [H, W, 3] -> f32 planes [P, packed_h, W] (knob2, wire layout)."""
    f = frame.astype(jnp.float32)
    b, g, r = f[..., 0], f[..., 1], f[..., 2]
    if cs == CS_BGR:
        return jnp.stack([b, g, r], axis=0)
    y = 0.114 * b + 0.587 * g + 0.299 * r
    if cs == CS_GRAY:
        return jnp.clip(jnp.round(y), 0, 255)[None]
    u = 0.492 * (b - y) + 128.0
    v = 0.877 * (r - y) + 128.0
    y8 = jnp.clip(jnp.round(y), 0, 255)
    u8 = jnp.clip(jnp.round(u[::2, ::2]), 0, 255)
    v8 = jnp.clip(jnp.round(v[::2, ::2]), 0, 255)
    return jnp.concatenate([y8, jnp.concatenate([u8, v8], axis=1)],
                           axis=0)[None]


def _artifact_masks(frame: jax.Array, bg: jax.Array, *,
                    thresh: float) -> tuple[jax.Array, jax.Array]:
    """knob4 keep-masks (movers, contours) of one uint8 [H, W, 3] frame
    against the raw background -- the exact semantics of
    ``knobs._artifact_removal``: channel-mean abs diff > thresh, cross
    dilation (false borders), contours = dilated minus its cross erosion
    (true borders)."""
    d = jnp.abs(frame.astype(jnp.float32) - bg.astype(jnp.float32))
    mask = d.mean(axis=-1) > thresh
    fr = jnp.zeros_like(mask[:1, :])
    fc = jnp.zeros_like(mask[:, :1])
    m = mask
    m = m | jnp.concatenate([fr, mask[:-1, :]], axis=0)
    m = m | jnp.concatenate([mask[1:, :], fr], axis=0)
    m = m | jnp.concatenate([fc, mask[:, :-1]], axis=1)
    m = m | jnp.concatenate([mask[:, 1:], fc], axis=1)
    tr = jnp.ones_like(m[:1, :])
    tc = jnp.ones_like(m[:, :1])
    er = m
    er = er & jnp.concatenate([tr, m[:-1, :]], axis=0)
    er = er & jnp.concatenate([m[1:, :], tr], axis=0)
    er = er & jnp.concatenate([tc, m[:, :-1]], axis=1)
    er = er & jnp.concatenate([m[:, 1:], tc], axis=1)
    return m, m & ~er


def proxy_features(payload: jax.Array) -> jax.Array:
    """Wire-size proxy features of a ``[..., P, oh, ow]`` payload batch:
    (sum log2(1+|d|), zero-delta count, |d|<=2 count) for horizontal and
    vertical byte deltas -- 6 values per payload, reduced over the last
    three axes.  The single definition serves the Pallas kernel, the ref
    oracle, and the CPU XLA twin in ``core.grid_engine``."""
    a = payload.astype(jnp.int32)
    dx = jnp.abs(a[..., :, 1:] - a[..., :, :-1]).astype(jnp.float32)
    dy = jnp.abs(a[..., 1:, :] - a[..., :-1, :]).astype(jnp.float32)
    axes = (-3, -2, -1)
    return jnp.stack([
        jnp.log2(1.0 + dx).sum(axes), (dx == 0).sum(axes).astype(jnp.float32),
        (dx <= 2).sum(axes).astype(jnp.float32),
        jnp.log2(1.0 + dy).sum(axes), (dy == 0).sum(axes).astype(jnp.float32),
        (dy <= 2).sum(axes).astype(jnp.float32),
    ], axis=-1)


def proxy_features_host(payload: np.ndarray) -> np.ndarray:
    """NumPy twin of ``proxy_features`` for one host payload (any shape with
    at least 2 dims; a 2-D payload is treated as one plane).  Used by
    ``CamBroker.fetch``'s per-frame candidate pre-screen, where dispatching
    a jitted op per frame would cost more than the feature math itself."""
    a = np.asarray(payload).astype(np.int64)
    if a.ndim == 2:
        a = a[None]                      # packed/gray -> one plane
    else:
        a = np.moveaxis(a, -1, 0)        # interleaved HxWxC -> planes
    dx = np.abs(a[:, :, 1:] - a[:, :, :-1]).astype(np.float32)
    dy = np.abs(a[:, 1:, :] - a[:, :-1, :]).astype(np.float32)
    return np.asarray([
        np.log2(1.0 + dx).sum(), float((dx == 0).sum()),
        float((dx <= 2).sum()),
        np.log2(1.0 + dy).sum(), float((dy == 0).sum()),
        float((dy <= 2).sum()),
    ], np.float32)


_HI = jax.lax.Precision.HIGHEST


def _mm(a: jax.Array, b: jax.Array) -> jax.Array:
    """``a @ b`` in full f32.  TPU's default f32 matmul is one bf16 pass,
    which would drop the low bits of the resize weights; HIGHEST keeps
    every product this kernel forms exact."""
    return jax.lax.dot_general(a, b, (((1,), (0,)), ((), ())),
                               precision=_HI,
                               preferred_element_type=jnp.float32)


def _mm_t(a: jax.Array, b: jax.Array) -> jax.Array:
    """``a @ b.T`` in full f32."""
    return jax.lax.dot_general(a, b, (((1,), (1,)), ((), ())),
                               precision=_HI,
                               preferred_element_type=jnp.float32)


def _eye_at(rows: int, cols: int, row0: int = 0, col0: int = 0,
            step: int = 1) -> jax.Array:
    """f32 [rows, cols] 0/1 matrix with ones at (row0 + i, col0 + step*i).

    Selection and placement by matmul: 2-D ``iota`` is all a Mosaic kernel
    needs, and a product against an exact 0/1 operand with one live term
    per output is exact, so subsampling and plane packing stay bit-exact
    without strided or unaligned slices."""
    r = jax.lax.broadcasted_iota(jnp.int32, (rows, cols), 0)
    c = jax.lax.broadcasted_iota(jnp.int32, (rows, cols), 1)
    return ((c - col0) == step * (r - row0)).astype(jnp.float32)


def _band3(n: int) -> jax.Array:
    """f32 [n, n] with ones where |i - j| <= 1 (a 3-tap neighbour count)."""
    r = jax.lax.broadcasted_iota(jnp.int32, (n, n), 0)
    c = jax.lax.broadcasted_iota(jnp.int32, (n, n), 1)
    return (jnp.abs(r - c) <= 1).astype(jnp.float32)


def _keep_mask(frame: list[jax.Array], bg: list[jax.Array],
               mode: jax.Array, *, thresh: float) -> jax.Array:
    """knob4 keep-mask (f32 0/1) of one planes-first frame, with
    ``_artifact_masks``' semantics: channel-mean |f - bg| > thresh (as the
    channel sum against 3x the threshold, which needs no division), cross
    dilation (false borders), contours = dilated minus its cross erosion
    (true borders).  Neighbour counts are band matmuls over 0/1 values, so
    they are exact.  ``mode`` (0 off, 1 movers, 2 contours) is a traced
    scalar, so one kernel instance serves the whole settings batch."""
    h, w = frame[0].shape
    d = (jnp.abs(frame[0] - bg[0]) + jnp.abs(frame[1] - bg[1])
         + jnp.abs(frame[2] - bg[2]))
    m = (d > 3.0 * thresh).astype(jnp.float32)
    by, bx = _band3(h), _band3(w)
    movers = ((_mm(by, m) + _mm(m, bx) - m) > 0).astype(jnp.float32)
    r = jax.lax.broadcasted_iota(jnp.int32, (h, w), 0)
    c = jax.lax.broadcasted_iota(jnp.int32, (h, w), 1)
    border = ((r == 0).astype(jnp.float32) + (r == h - 1).astype(jnp.float32)
              + (c == 0).astype(jnp.float32)
              + (c == w - 1).astype(jnp.float32))
    cross = _mm(by, movers) + _mm(movers, bx) - movers + border
    contours = movers * (cross < 5.0).astype(jnp.float32)
    on = lambda k: jnp.where(mode == k, 1.0, 0.0).astype(jnp.float32)
    return on(0) + on(1) * movers + on(2) * contours


def _round_div(num: jax.Array, den) -> jax.Array:
    """Round-half-even of ``num / den``, exactly: int32 ``num`` (either
    sign, ``|num| < 2**30``) over a positive integer ``den`` (a Python int
    or an int32 array).  The f32 quotient is only an estimate; the integer
    remainder corrects it, so the result does not depend on how a backend
    rounds a division."""
    q = jnp.floor(num.astype(jnp.float32) / jnp.float32(den)
                  ).astype(jnp.int32)
    r = num - q * den
    q = jnp.where(r < 0, q - 1, jnp.where(r >= den, q + 1, q))
    r = num - q * den
    up = (2 * r > den) | ((2 * r == den) & ((q & 1) == 1))
    return q + up.astype(jnp.int32)


def _colorspace(b: jax.Array, g: jax.Array, r: jax.Array, cs: int
                ) -> list[jax.Array]:
    """knob2 on int32 planes -> the wire planes as integer-valued f32:
    BGR, gray, or 4:2:0 YUV packed as one [h + h/2, w] plane (Y on top,
    U|V below).  ``_to_planes``' coefficients (0.114/0.587/0.299 luma,
    0.492/0.877 chroma) are applied in exact integer arithmetic, so a
    value on a rounding tie rounds half-even on every backend.  Chroma is
    computed from the even-position samples, which equals computing it at
    full resolution and subsampling."""
    f32 = lambda x: x.astype(jnp.float32)
    if cs == CS_BGR:
        return [f32(b), f32(g), f32(r)]
    y8 = jnp.clip(_round_div(114 * b + 587 * g + 299 * r, 1000), 0, 255)
    if cs == CS_GRAY:
        return [f32(y8)]
    h, w = b.shape
    sel_y, sel_x = _eye_at(h // 2, h, step=2), _eye_at(w // 2, w, step=2)
    be, ge, re = (_mm_t(_mm(sel_y, f32(p)), sel_x).astype(jnp.int32)
                  for p in (b, g, r))
    y1000 = 114 * be + 587 * ge + 299 * re
    u8 = jnp.clip(_round_div(492 * (1000 * be - y1000), 10 ** 6) + 128,
                  0, 255)
    v8 = jnp.clip(_round_div(877 * (1000 * re - y1000), 10 ** 6) + 128,
                  0, 255)
    uv = (_mm(f32(u8), _eye_at(w // 2, w))
          + _mm(f32(v8), _eye_at(w // 2, w, col0=w // 2)))
    hp = h + h // 2
    return [_mm(_eye_at(hp, h), f32(y8))
            + _mm(_eye_at(hp, h // 2, row0=h), uv)]


RESIZE_BITS = 15   # resize taps live on the 2**-15 grid


def _resize(plane: jax.Array, ry: jax.Array, rx: jax.Array) -> jax.Array:
    """knob1: ``round(ry @ plane @ rx.T)``, half-even, computed exactly.

    ``plane`` holds integers <= 255 and the taps are multiples of 2**-15
    whose rows sum to 1, so ``t = ry @ plane`` is exact in f32.  The second
    product would need 39 bits, so ``t`` is split into an integer part and
    two fraction limbs of at most 8 bits each; every limb's product with
    ``rx`` is exact, and the rounding of their sum is decided from the
    exact limbs.  No step depends on a backend's summation order."""
    t = _mm(ry, plane)
    th = jnp.floor(t)
    tl1 = jnp.floor((t - th) * 128.0) / 128.0          # fraction bits 1-7
    tl2 = t - th - tl1                                  # fraction bits 8-15
    a = _mm_t(th, rx)                                   # 2**-15 grid, <= 255
    b1 = _mm_t(tl1, rx)                                 # 2**-22 grid, < 1
    b2 = _mm_t(tl2, rx)                                 # 2**-30 grid, < 2**-7
    base = jnp.floor(a)
    f = a - base + b1                                   # 2**-22 grid, < 2
    base, f = base + jnp.floor(f), f - jnp.floor(f)
    b2h = jnp.floor(b2 * 2.0 ** 22) / 2.0 ** 22
    f = f + b2h                                         # 2**-22 grid, < 2
    base, f = base + jnp.floor(f), f - jnp.floor(f)
    rest = b2 - b2h                                     # [0, 2**-22)
    odd = base - 2.0 * jnp.floor(0.5 * base)
    up = (f > 0.5) | ((f == 0.5) & ((rest > 0) | (odd == 1.0)))
    return jnp.clip(base + up.astype(jnp.float32), 0, 255)


def _blur(rs: jax.Array, by: jax.Array, bx: jax.Array) -> jax.Array:
    """knob3: the box blur as integer tap counts -- ``by @ rs @ bx.T`` is
    an exact integer sum, and each count row sums to the blur width, so
    the mean is that sum over ``ky * kx``, rounded half-even exactly."""
    total = _mm_t(_mm(by, rs), bx).astype(jnp.int32)
    den = (jnp.sum(by[0:1, :], keepdims=True)
           * jnp.sum(bx[0:1, :], keepdims=True)).astype(jnp.int32)
    return _round_div(total, den).astype(jnp.float32)


def exact_operators(plan: "TransformPlan") -> tuple[np.ndarray, ...]:
    """The plan's operators in the form ``frame_knob_grid`` computes with:
    resize taps rounded to the 2**-15 grid (each row still sums to exactly
    1) and blur bands as integer tap counts.  Returns ``(ry, rx, bys,
    bxs)``, f32."""
    def grid(m):
        scale = 2.0 ** RESIZE_BITS
        q = np.rint(m.astype(np.float64) * scale)
        q[np.arange(len(q)), q.argmax(axis=1)] += scale - q.sum(axis=1)
        return (q / scale).astype(np.float32)

    widths = np.maximum(plan.blur_ids, 1).astype(np.float64)[:, None, None]
    return (grid(plan.ry), grid(plan.rx),
            np.rint(plan.bys * widths).astype(np.float32),
            np.rint(plan.bxs * widths).astype(np.float32))


def _plane_features(p: jax.Array) -> list[jax.Array]:
    """``proxy_features``' six sums for one [oh, ow] payload plane, each as
    a [1, 1] value.  Byte deltas come from exact +-1 difference operators
    (no unaligned slices); the last row/column, which has no neighbour, is
    masked out."""
    oh, ow = p.shape
    dx = jnp.abs(_mm(p, _eye_at(ow, ow, row0=1)
                     - _eye_at(ow, ow)))                  # p[:, k+1] - p[:, k]
    dy = jnp.abs(_mm(_eye_at(oh, oh, col0=1) - _eye_at(oh, oh), p))
    col = jax.lax.broadcasted_iota(jnp.int32, (oh, ow), 1)
    row = jax.lax.broadcasted_iota(jnp.int32, (oh, ow), 0)
    out = []
    for dd, valid in ((dx, col < ow - 1), (dy, row < oh - 1)):
        vf = valid.astype(jnp.float32)
        for term in (jnp.log2(1.0 + dd), (dd == 0).astype(jnp.float32),
                     (dd <= 2).astype(jnp.float32)):
            out.append(jnp.sum(term * vf, keepdims=True))
    return out


def _u8_f32(x: jax.Array) -> jax.Array:
    """uint8 -> f32 through int32 (Mosaic has no direct 8-bit float cast)."""
    return x.astype(jnp.int32).astype(jnp.float32)


STATS_LANES = 128   # lane-dense per-program stats row: 6 feats + changed


def _grid_compute(frame: jax.Array, prev: jax.Array, ry: jax.Array,
                  rx: jax.Array, by: jax.Array, bx: jax.Array, *,
                  cs: int, pixel_delta: float,
                  bg: jax.Array | None = None,
                  art_mode: jax.Array | None = None,
                  art_thresh: float = ARTIFACT_THRESH,
                  ) -> tuple[list[jax.Array], jax.Array]:
    """The fused per-(setting, frame) pipeline on planes-first uint8
    ``[3, H, W]`` inputs (W on the lanes), shared op-for-op by the kernel
    and its oracle.  Returns the uint8 payload planes and a ``[1, 128]``
    f32 stats row: the six proxy features in lanes 0-5 and the knob5
    changed-pixel COUNT in lane 6."""
    fi = [frame[c].astype(jnp.int32) for c in range(3)]
    f = [x.astype(jnp.float32) for x in fi]
    pv = [_u8_f32(prev[c]) for c in range(3)]
    # knob5 change metric on the raw frame (channel-mean > delta, as the
    # channel sum against 3x delta) -- measured BEFORE knob4, matching
    # ``knobs.apply_knobs``' pipeline order
    d = (jnp.abs(f[0] - pv[0]) + jnp.abs(f[1] - pv[1])
         + jnp.abs(f[2] - pv[2]))
    changed = jnp.sum((d > 3.0 * pixel_delta).astype(jnp.float32),
                      keepdims=True)

    if bg is not None:
        keep = _keep_mask(f, [_u8_f32(bg[c]) for c in range(3)],
                          art_mode, thresh=art_thresh)
        fi = [x * keep.astype(jnp.int32) for x in fi]
    payload, feats = [], None
    for plane in _colorspace(*fi, cs):
        bl = _blur(_resize(plane, ry, rx), by, bx)
        pf = _plane_features(bl)
        feats = pf if feats is None else [a + b for a, b in zip(feats, pf)]
        payload.append(bl.astype(jnp.int32).astype(jnp.uint8))

    lane = jax.lax.broadcasted_iota(jnp.int32, (1, STATS_LANES), 1)
    stats = jnp.zeros((1, STATS_LANES), jnp.float32)
    for k, v in enumerate(feats + [changed]):
        stats = stats + jnp.where(lane == k, v, 0.0)
    return payload, stats


def _grid_kernel(*refs, cs: int, pixel_delta: float, art_thresh: float,
                 with_art: bool):
    if with_art:
        (am_ref, en_ref, f_ref, p_ref, bg_ref, ry_ref, rx_ref, by_ref,
         bx_ref, o_ref, st_ref) = refs
        # per-frame enable gates knob4 off for the background / padding
        # frames
        mode = am_ref[pl.program_id(0)] * en_ref[pl.program_id(1)]
        bg = bg_ref[...]
    else:
        f_ref, p_ref, ry_ref, rx_ref, by_ref, bx_ref, o_ref, st_ref = refs
        mode = bg = None
    payload, stats = _grid_compute(
        f_ref[0], p_ref[0], ry_ref[...], rx_ref[...], by_ref[0], bx_ref[0],
        cs=cs, pixel_delta=pixel_delta, bg=bg, art_mode=mode,
        art_thresh=art_thresh)
    for i, plane in enumerate(payload):
        o_ref[0, 0, i] = plane
    st_ref[0, 0] = stats


@functools.partial(jax.jit, static_argnames=("cs", "geom", "pixel_delta",
                                             "art_thresh", "interpret"))
def _grid_call(frames, prev, ry, rx, bys, bxs, *, cs, geom, pixel_delta,
               interpret, bg=None, art_enable=None, art_ids=None,
               art_thresh=ARTIFACT_THRESH):
    """frames/prev: planes-first uint8 ``[F, 3, H, W]``; bg ``[3, H, W]``.

    Per-setting and per-frame knob4 ints ride as scalar prefetch (SMEM);
    every VMEM block's last two dims are whole array dims, with W on the
    128-lane axis."""
    h, w, packed_h, out_h, out_w, n_planes = geom
    s = bys.shape[0]
    f = frames.shape[0]
    with_art = bg is not None
    kernel = functools.partial(_grid_kernel, cs=cs, pixel_delta=pixel_delta,
                               art_thresh=art_thresh, with_art=with_art)
    n_pre = 2 if with_art else 0
    ix = lambda fn: (lambda i, j, *_: fn(i, j))
    frame_spec = pl.BlockSpec((1, 3, h, w), ix(lambda i, j: (j, 0, 0, 0)))
    in_specs = [frame_spec, frame_spec]
    if with_art:
        in_specs.append(pl.BlockSpec((3, h, w), ix(lambda i, j: (0, 0, 0))))
    in_specs += [
        pl.BlockSpec((out_h, packed_h), ix(lambda i, j: (0, 0))),
        pl.BlockSpec((out_w, w), ix(lambda i, j: (0, 0))),
        pl.BlockSpec((1, out_h, out_h), ix(lambda i, j: (i, 0, 0))),
        pl.BlockSpec((1, out_w, out_w), ix(lambda i, j: (i, 0, 0))),
    ]
    out_specs = [
        pl.BlockSpec((1, 1, n_planes, out_h, out_w),
                     ix(lambda i, j: (i, j, 0, 0, 0))),
        pl.BlockSpec((1, 1, 1, STATS_LANES), ix(lambda i, j: (i, j, 0, 0))),
    ]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=n_pre, grid=(s, f), in_specs=in_specs,
        out_specs=out_specs)
    pre = (art_ids, art_enable) if with_art else ()
    extra = (bg,) if with_art else ()
    payload, stats = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((s, f, n_planes, out_h, out_w), jnp.uint8),
            jax.ShapeDtypeStruct((s, f, 1, STATS_LANES), jnp.float32),
        ],
        interpret=interpret,
    )(*pre, frames, prev, *extra, ry, rx, bys, bxs)
    return _split_stats(payload, stats, h * w)


def _split_stats(payload, stats, pixels: int):
    """(payload, stats rows) -> (payload, feats [S, F, 6], changed [S, F])."""
    return (payload, stats[:, :, 0, :N_PROXY_FEATURES],
            stats[:, :, 0, N_PROXY_FEATURES] / pixels)


def frame_knob_grid(frames: jax.Array, prev: jax.Array, plan: TransformPlan,
                    *, background: jax.Array | None = None,
                    art_enable: jax.Array | None = None,
                    pixel_delta: float = 8.0,
                    art_thresh: float = ARTIFACT_THRESH,
                    interpret: bool = False
                    ) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Evaluate one plan's settings batch over a clip in a single HBM pass.

    frames/prev: uint8 ``[F, H, W, 3]`` (prev = the clip shifted by one for
    the knob5 metric).  Plans with knob4 settings additionally need
    ``background`` (uint8 ``[H, W, 3]``, the raw background model) and may
    pass ``art_enable`` (i32 ``[F]``, default all-on) to exempt individual
    frames -- ``core.grid_engine`` exempts the background/padding frames it
    prepends for the detector diff.  Returns

      payload [S, F, P, out_h, out_w] uint8   the shipped representation
                                              (P planes: b/g/r, or one
                                              gray / packed-yuv plane),
      feats   [S, F, 6] f32                   wire-size proxy features,
      changed [S, F] f32                      knob5 changed-pixel fraction
                                              (setting-independent: every
                                              row carries the same values).
    """
    n, h, w, c = frames.shape
    assert (h, w) == (plan.in_h, plan.in_w) and c == 3, (frames.shape, plan)
    geom = (plan.in_h, plan.in_w, plan.packed_h, plan.out_h, plan.out_w,
            plan.n_planes)
    if plan.with_artifact and background is None:
        raise ValueError("plan batches knob4 settings; pass background=")
    kwargs = {}
    if background is not None:
        if art_enable is None:
            art_enable = jnp.ones((n,), jnp.int32)
        kwargs = dict(bg=jnp.transpose(jnp.asarray(background), (2, 0, 1)),
                      art_enable=jnp.asarray(art_enable, jnp.int32),
                      art_ids=jnp.asarray(plan.art_ids),
                      art_thresh=art_thresh)
    return _grid_call(jnp.transpose(frames, (0, 3, 1, 2)),
                      jnp.transpose(prev, (0, 3, 1, 2)),
                      *map(jnp.asarray, exact_operators(plan)),
                      cs=plan.cs, geom=geom, pixel_delta=pixel_delta,
                      interpret=interpret, **kwargs)
