"""Batched device-side characterization engine (the knob grid in one sweep).

The seed ``characterize()`` walked ~450 settings x calibration frames one at
a time through NumPy transforms, zlib, and an iterative host detector --
minutes of wall clock for a table the paper assumes "available from prior
characterization".  This engine evaluates the whole grid as device-resident
batches so characterization is cheap enough to re-run live on QoS
renegotiation (CANS-style online self-configuration):

  1. **Transform stage** -- the knob pipeline (colorspace -> resize -> blur)
     for every (resolution, colorspace, blur) combo runs as batched einsums
     over operator matrices from ``kernels.frame_knobs.build_transform_plan``
     (one ``[n_settings, frames, ...]`` pass per (resolution, colorspace)
     group).  On TPU the fused Pallas kernel ``frame_knob_grid`` runs
     instead; on CPU its XLA twin compiles to the same math batched over the
     settings dimension.
  2. **Wire-size proxy** -- per-payload byte-delta statistics (computed in
     the same pass) are calibrated against zlib level-1 on one frame per
     combo, then predict the wire size of every (setting, frame).  Deflate
     runs ~75 times per characterization instead of ~1800; the stream path
     (``CamBroker.fetch`` -> ``knobs.wire_size``) keeps exact zlib for the
     frames actually sent.
  3. **Detector scoring** -- background diff and the proxy features run
     batched over the settings dimension on device; thresholding, dilation,
     and component labeling run vectorized over the ``[settings, frames]``
     batch (scipy's C labeling on CPU; on TPU, where host round-trips are
     the enemy, ``_label_group``: rounds of gather-free segmented min scans
     along rows and columns, run to their fixpoint).
     Box extraction is segment-vectorized per frame (lexsort + reduceat),
     semantically identical to ``detector.boxes_from_labels``.  The
     adaptive threshold's median/percentile use NumPy's introselect (XLA's
     sort is ~10x slower here) with the same numerics as
     ``detector.detect``.
  4. **knob5 change metric** -- pairwise changed-pixel counts between clip
     frames in one device pass; drop patterns for every DIFF_THRESHOLD are
     derived from the matrix with ``frame_difference``'s exact semantics.

``characterization.characterize`` drives this engine by default and keeps
the seed per-frame NumPy path as the reference oracle.
"""

from __future__ import annotations

import dataclasses
import functools
import zlib

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation

from repro.core import detector as det
from repro.core import knobs as K
from repro.kernels import frame_knobs as FK

__all__ = ["GridCharacterization", "WireSizeProxy", "run_grid",
           "stage_clip", "refresh_tables", "label_rounds", "PIXEL_DELTA"]

PIXEL_DELTA = 8.0        # knobs.frame_difference's noise-robust change delta
_FRAME_BUCKET = 16       # frame-axis padding so jit caches are shared
_MIN_WIRE_BYTES = 16.0   # proxy floor: a deflate stream is never smaller


# =============================================================================
# Device stages
# =============================================================================


def _payload_gray(payload: jax.Array) -> jax.Array:
    """Detector gray plane of a [..., P, oh, ow] payload batch (the same
    channel weights as ``detector._to_gray``; packed yuv/gray payloads are
    their own gray plane)."""
    pf = payload.astype(jnp.float32)
    if payload.shape[-3] == 3:
        return (0.114 * pf[..., 0, :, :] + 0.587 * pf[..., 1, :, :]
                + 0.299 * pf[..., 2, :, :])
    return pf[..., 0, :, :]


@functools.partial(jax.jit, static_argnames=("cs", "art_modes"))
def _transform_group(frames: jax.Array, ry, rx, bys, bxs, cs: int,
                     bg=None, enable=None,
                     art_modes: tuple[int, ...] = (0,)):
    """XLA twin of the Pallas ``frame_knob_grid``, batched over (settings,
    frames): payload u8 [S,F,P,oh,ow], proxy feats [S,F,6], and the
    detector's background diff [S,F-1,gh,gw] (frame 0 is the background).

    The twin computes the knob pipeline in plain f32 (``_to_planes``,
    ``_artifact_masks`` and the plan's float operators); the kernel rounds
    the same stages exactly, so the two can differ by one grey level where
    a value sits on a rounding tie.  ``art_modes``
    is the plan's own mode tuple (artifact-major setting blocks of
    ``S // len(art_modes)`` blur settings each): each block applies the
    mask of its ACTUAL mode id, exactly like the kernel's per-setting
    ``art_ids``.  ``enable`` exempts the background/padding frames from
    knob4.
    """
    n_art = len(art_modes)

    def pipeline(fr):
        planes = jax.vmap(lambda f: FK._to_planes(f, cs))(fr)     # [F,P,Hc,W]
        rs = jnp.einsum("ah,fphw->fpaw", ry, planes)              # knob1
        rs = jnp.einsum("bw,fpaw->fpab", rx, rs)
        return jnp.clip(jnp.round(rs), 0, 255)

    if art_modes == (0,):
        resized = pipeline(frames)[None]                          # [1,F,P,a,b]
    else:
        movers, contours = jax.vmap(
            lambda f: FK._artifact_masks(f, bg, thresh=FK.ARTIFACT_THRESH)
        )(frames)
        off = (enable == 0)[:, None, None]
        keep_of_mode = {0: None, 1: movers | off, 2: contours | off}
        resized = jnp.stack([
            pipeline(frames if keep_of_mode[mode] is None
                     else jnp.where(keep_of_mode[mode][..., None], frames,
                                    jnp.zeros_like(frames)))
            for mode in art_modes])                               # [A,F,P,a,b]

    s = bys.shape[0]
    per = s // n_art
    bl = jnp.concatenate([
        jnp.einsum("sab,fpbw->sfpaw", bys[a * per:(a + 1) * per], resized[a])
        for a in range(n_art)])                                   # knob3
    bl = jnp.einsum("scw,sfpaw->sfpac", bxs, bl)
    payload = jnp.clip(jnp.round(bl), 0, 255).astype(jnp.uint8)

    feats = FK.proxy_features(payload)
    gray = _payload_gray(payload)
    diff = jnp.abs(gray[:, 1:] - gray[:, :1])
    return payload, feats, diff


@jax.jit
def _payload_diff(payload: jax.Array):
    """Background diff from a Pallas-produced payload batch (TPU path)."""
    gray = _payload_gray(payload)
    return jnp.abs(gray[:, 1:] - gray[:, :1])


def _foreground(diff: jax.Array, eff: jax.Array) -> jax.Array:
    """Threshold -> cross dilation of a [s, f, gh, gw] diff batch, as one
    [s*f, gh, gw] bool batch (``detector.dilate_cross``'s semantics)."""
    s, f, gh, gw = diff.shape
    mask = diff > eff[:, :, None, None]
    fr = jnp.zeros_like(mask[:, :, :1, :])
    fc = jnp.zeros_like(mask[:, :, :, :1])
    m = mask
    m = m | jnp.concatenate([fr, mask[:, :, :-1, :]], axis=2)
    m = m | jnp.concatenate([mask[:, :, 1:, :], fr], axis=2)
    m = m | jnp.concatenate([fc, mask[:, :, :, :-1]], axis=3)
    m = m | jnp.concatenate([mask[:, :, :, 1:], fc], axis=3)
    return m.reshape(s * f, gh, gw)


def _run_offsets(fg: jax.Array, axis: int, stride: int) -> jax.Array:
    """``stride`` x the 1-based index of each pixel's run of foreground
    along ``axis`` (a background pixel carries the last run before it)."""
    before = jax.lax.slice_in_dim(fg, 0, fg.shape[axis] - 1, axis=axis)
    before = jnp.concatenate(
        [jnp.zeros_like(jax.lax.slice_in_dim(fg, 0, 1, axis=axis)), before],
        axis=axis)
    return jnp.cumsum(fg & ~before, axis=axis, dtype=jnp.int32) * stride


def _label_fixpoint(fg: jax.Array) -> tuple[jax.Array, jax.Array]:
    """4-connected components of a [B, gh, gw] bool batch by segmented min
    scans: ``(ids, rounds)``, ids the min flat index of each pixel's
    component (``gh*gw`` on background), rounds the scan rounds run.

    One round: every foreground pixel takes the min id of its run of
    foreground along its row (a forward then a backward scan), then along
    its column.  Two 4-adjacent foreground pixels share a row run or a
    column run and a round only lowers ids within a component, so the
    fixpoint is each component's min flat index.  A run is made a segment
    of a plain cumulative min by offsetting ids with ``(gh*gw + 1)`` x the
    run's index: an earlier run (forward) or a later one (backward) then
    never wins, and no gather is needed.
    """
    _, gh, gw = fg.shape
    big = gh * gw
    ids0 = jnp.where(fg, jnp.arange(big, dtype=jnp.int32).reshape(gh, gw),
                     big)
    offsets = [(axis, _run_offsets(fg, axis, big + 1)) for axis in (2, 1)]

    def step(ids):
        for axis, off in offsets:
            ids = jnp.where(fg, jax.lax.cummin(ids - off, axis=axis) + off,
                            big)
            ids = jnp.where(fg, jax.lax.cummin(ids + off, axis=axis,
                                               reverse=True) - off, big)
        return ids

    def cond(carry):
        ids, prev, _ = carry
        return jnp.any(ids != prev)

    def body(carry):
        ids, _, rounds = carry
        return step(ids), ids, rounds + 1

    ids, _, rounds = jax.lax.while_loop(
        cond, body, (step(ids0), ids0, jnp.int32(1)))
    return ids, rounds


@jax.jit
def _label_group(diff: jax.Array, eff: jax.Array) -> jax.Array:
    """Threshold -> cross dilation -> 4-connected components, batched.

    Labels are min-flat-index per component (the same fixpoint as
    ``detector._label``); background pixels carry the ``gh*gw`` sentinel.
    Each round is a forward and a backward segmented min scan along every
    row, then along every column (``_label_fixpoint``): streamed cumulative
    ops, no gather, and a handful of rounds where four-neighbour
    propagation takes one per pixel of a component's diameter.
    """
    ids, _ = _label_fixpoint(_foreground(diff, eff))
    return ids.reshape(diff.shape)


def label_rounds(diff, eff) -> int:
    """The scan rounds ``_label_group``'s fixpoint takes on ``(diff, eff)``
    (the largest over the batch, the last round, which changes nothing,
    included)."""
    return int(_label_rounds(jnp.asarray(diff), jnp.asarray(eff)))


@jax.jit
def _label_rounds(diff: jax.Array, eff: jax.Array) -> jax.Array:
    return _label_fixpoint(_foreground(diff, eff))[1]


@jax.jit
def _change_counts(frames: jax.Array) -> jax.Array:
    """Pairwise knob5 change counts: out[i, j] = #pixels of frame i whose
    channel-mean abs-difference from frame j exceeds PIXEL_DELTA."""
    f = frames.astype(jnp.float32)

    def row(i):
        d = jnp.abs(f - f[i]).mean(axis=-1)
        return (d > PIXEL_DELTA).sum(axis=(1, 2)).astype(jnp.int32)

    n = frames.shape[0]
    return jnp.transpose(jax.lax.map(row, jnp.arange(n)))


# =============================================================================
# Wire-size proxy (byte-delta features -> calibrated deflate estimate)
# =============================================================================


@dataclasses.dataclass
class WireSizeProxy:
    """Per-(colorspace, knob4-on/off) linear model: zlib_level1_bytes ~=
    coeffs . [n_bytes, feats(6), 1].  Calibrated per characterization run on
    one real deflate measurement per (resolution, colorspace, blur, artifact)
    combo, so the estimate tracks the scene's actual texture statistics.
    Artifact-removed payloads (mostly zeros, long deflate runs) live in a
    different compression regime than dense ones, hence the separate fit."""
    coeffs: np.ndarray                  # [3, 2, 8]
    median_rel_err: float               # on the calibration pairs
    max_rel_err: float

    def predict(self, cs: int, payload_bytes: int, feats: np.ndarray, *,
                art: bool = False) -> np.ndarray:
        x = np.concatenate([
            np.full(feats.shape[:-1] + (1,), float(payload_bytes)),
            np.asarray(feats, np.float64),
            np.ones(feats.shape[:-1] + (1,))], axis=-1)
        return np.maximum(x @ self.coeffs[cs, int(art)], _MIN_WIRE_BYTES)


def _fit_proxy(samples: list[tuple[int, int, int, np.ndarray, int]]
               ) -> WireSizeProxy:
    """samples: (cs, art, payload_bytes, feats[6], zlib_bytes) rows."""
    coeffs = np.zeros((3, 2, FK.N_PROXY_FEATURES + 2))
    rels: list[float] = []
    for cs in range(3):
        for art in range(2):
            rows = [s for s in samples if s[0] == cs and (s[1] > 0) == art]
            if not rows:
                continue
            a = np.stack([np.concatenate([[n], f, [1.0]])
                          for _, _, n, f, _ in rows])
            y = np.asarray([z for *_, z in rows], np.float64)
            coeffs[cs, art], *_ = np.linalg.lstsq(a, y, rcond=None)
            pred = np.maximum(a @ coeffs[cs, art], _MIN_WIRE_BYTES)
            rels.extend(np.abs(pred - y) / np.maximum(y, 1.0))
    rels_arr = np.asarray(rels) if rels else np.zeros(1)
    return WireSizeProxy(coeffs, float(np.median(rels_arr)),
                         float(rels_arr.max()))


def _wire_payload(payload_sf: np.ndarray, cs: int) -> np.ndarray:
    """Planes -> the exact on-the-wire byte layout (interleaved for BGR)."""
    if cs == FK.CS_BGR:
        return np.ascontiguousarray(np.moveaxis(payload_sf, 0, -1))
    return np.ascontiguousarray(payload_sf[0])


# =============================================================================
# The engine
# =============================================================================


@dataclasses.dataclass
class GridCharacterization:
    """Everything ``characterize()`` needs, for every (resolution,
    colorspace, blur, artifact) combo over the calibration clip.  Combos
    are 4-tuples; without ``include_artifact`` the artifact slot is 0."""
    combos: tuple[tuple[int, int, int, int], ...]
    dets: dict[tuple[int, int, int, int], list[np.ndarray]]  # boxes, orig coords
    sizes: dict[tuple[int, int, int, int], np.ndarray]       # [F] proxy bytes
    change_counts: np.ndarray                            # [F, F] int32
    pixels: int                                          # H*W of the camera
    proxy: WireSizeProxy
    zlib_calls: int
    include_artifact: bool = False

    def change_fraction(self, i: int, j: int) -> float:
        """frame_difference's dissimilarity between clip frames i and j,
        bit-equal to the host computation (integer count / pixel count)."""
        return float(self.change_counts[i, j]) / self.pixels

    def drop_pattern(self, threshold: float) -> np.ndarray:
        """knob5 drop decisions over the clip for one DIFF_THRESHOLD, with
        ``frame_difference``'s exact walk semantics (compare against the
        last *sent* frame; threshold < 0 disables)."""
        n = self.change_counts.shape[0]
        drops = np.zeros(n, bool)
        if threshold < 0.0:
            return drops
        last: int | None = None
        for i in range(n):
            if last is not None and self.change_fraction(i, last) <= threshold:
                drops[i] = True
            else:
                last = i
        return drops


def _segment_boxes_batch(labels: np.ndarray, diff: np.ndarray, *,
                         background_label: int, sy: float, sx: float,
                         min_px: float) -> list[np.ndarray]:
    """Segment-vectorized twin of ``detector.boxes_from_labels`` over a
    whole [B, gh, gw] image batch: ONE lexsort + reduceat pass for every
    component of every image, keyed by (image, label).  Same semantics per
    image (ascending-label order, half-maximum refinement via the
    95th-percentile peak with linear interpolation); agreement with the
    host helper is asserted by the characterization oracle tests."""
    n_img, gh, gw = labels.shape
    flat = labels.reshape(n_img, -1)
    fg_img, fg_pix = np.nonzero(flat != background_label)
    empty = np.zeros((0, 4), np.float32)
    if not fg_img.size:
        return [empty] * n_img
    big = gh * gw
    lab = flat[fg_img, fg_pix].astype(np.int64)
    d = diff.reshape(n_img, -1)[fg_img, fg_pix]
    key = fg_img * np.int64(big + 1) + lab
    order = np.lexsort((d, key))
    key_s, d_s = key[order], d[order]
    starts = np.flatnonzero(np.r_[True, key_s[1:] != key_s[:-1]])
    ends = np.append(starts[1:], key_s.size)
    lens = ends - starts
    keep = lens >= min_px
    # per-segment 95th percentile of diff (d is sorted within each segment)
    v = (lens - 1) * 0.95
    lo = np.floor(v).astype(np.int64)
    frac = v - lo
    a = d_s[starts + lo]
    b = d_s[np.minimum(starts + lo + 1, ends - 1)]
    peak = a + frac * (b - a)
    strong = d_s >= 0.5 * np.repeat(peak, lens)
    n_strong = np.add.reduceat(strong, starts)
    sel = strong | np.repeat(n_strong < 2, lens)
    ys, xs = np.divmod(fg_pix[order], gw)
    ymin = np.minimum.reduceat(np.where(sel, ys, big), starts)[keep]
    ymax = np.maximum.reduceat(np.where(sel, ys, -1), starts)[keep]
    xmin = np.minimum.reduceat(np.where(sel, xs, big), starts)[keep]
    xmax = np.maximum.reduceat(np.where(sel, xs, -1), starts)[keep]
    boxes = np.stack([ymin * sy, xmin * sx, (ymax + 1) * sy,
                      (xmax + 1) * sx], axis=1).astype(np.float32)
    # split back per image: segments are sorted by (image, label)
    seg_img = fg_img[order][starts][keep]
    bounds = np.searchsorted(seg_img, np.arange(n_img + 1))
    return [boxes[bounds[i]:bounds[i + 1]] for i in range(n_img)]


def _segment_boxes(labels: np.ndarray, diff: np.ndarray, *,
                   background_label: int, sy: float, sx: float,
                   min_px: float) -> np.ndarray:
    """Single-image convenience wrapper over ``_segment_boxes_batch``."""
    return _segment_boxes_batch(labels[None], diff[None],
                                background_label=background_label,
                                sy=sy, sx=sx, min_px=min_px)[0]


def _label_host(mask: np.ndarray) -> tuple[np.ndarray, int]:
    """4-connected labeling of a [B, gh, gw] bool batch via scipy's C
    implementation (raster-discovery label order == the ascending
    min-flat-index order of the device labeler)."""
    from scipy import ndimage               # declared dep; fallback below
    out = np.empty(mask.shape, np.int32)
    for i in range(mask.shape[0]):
        ndimage.label(mask[i], output=out[i])
    return out, 0                                   # background label


def stage_clip(background: np.ndarray, frames: list[np.ndarray]):
    """The grid sweep's device inputs for one calibration clip: the frame
    stack ``[background, *frames, padding]`` (padded with the background
    to a ``_FRAME_BUCKET`` multiple so jit caches are shared), the same
    stack shifted by one (knob5's previous frames), the background, and
    knob4's per-frame enable -- off for frame 0 (the detector's background
    payload) and the padding tail.  Returns ``(frames, prev, background,
    enable)`` as device arrays."""
    n_real = len(frames) + 1
    n_pad = -(-n_real // _FRAME_BUCKET) * _FRAME_BUCKET
    stack = np.stack([background] + list(frames)
                     + [background] * (n_pad - n_real)).astype(np.uint8)
    enable = np.zeros(n_pad, np.int32)
    enable[1:n_real] = 1
    return (jnp.asarray(stack),
            jnp.asarray(np.concatenate([stack[:1], stack[:-1]])),
            jnp.asarray(background.astype(np.uint8)), jnp.asarray(enable))


def run_grid(background: np.ndarray, frames: list[np.ndarray], *,
             detector_thresh: float = 28.0, min_area: int = 12,
             include_artifact: bool = False,
             use_pallas: bool | None = None) -> GridCharacterization:
    """Characterize every (resolution, colorspace, blur[, artifact]) combo
    over a clip.

    ``background``/``frames``: uint8 [H, W, 3] with even H, W (the Pallas /
    XLA grid path needs 4:2:0-subsample-able planes; ``characterize`` falls
    back to the NumPy reference engine otherwise).  ``include_artifact``
    triples the settings batch of every group with knob4's movers/contours
    modes, run device-side against the raw background.

    Device work is dispatched with a bounded lookahead (JAX dispatch is
    asynchronous), so transforms for the next groups overlap the host-side
    scoring of the current one without holding all 15 groups' payload/diff
    buffers resident at once.
    """
    h, w = background.shape[:2]
    if background.ndim != 3 or background.shape[2] != 3 or h % 2 or w % 2:
        raise ValueError(f"grid engine needs even-dim 3-channel frames, "
                         f"got {background.shape}")
    if use_pallas is None:
        # The fused kernel lowers through Mosaic; every other backend takes
        # the XLA twin (same math, batched einsums).
        use_pallas = jax.default_backend() == "tpu"

    art_modes = (0, 1, 2) if include_artifact else (0,)
    n_clip = len(frames)
    n_real = n_clip + 1                                  # +1: background
    fj, prevj, bgj, enj = stage_clip(background, frames)

    change_counts_dev = _change_counts(
        jnp.asarray(np.stack(frames).astype(np.uint8)))

    def dispatch(res_cs: tuple[int, int]):
        res, cs = res_cs
        plan = FK.build_transform_plan(
            h, w, scale=K.RESOLUTION_SCALES[res], cs=cs,
            blur_ks=K.BLUR_KERNELS, art_modes=art_modes)
        if use_pallas:
            payload, feats, _ = FK.frame_knob_grid(
                fj, prevj, plan,
                background=bgj if include_artifact else None,
                art_enable=enj if include_artifact else None)
            diff = _payload_diff(payload)
        else:
            payload, feats, diff = _transform_group(
                fj, jnp.asarray(plan.ry), jnp.asarray(plan.rx),
                jnp.asarray(plan.bys), jnp.asarray(plan.bxs), cs,
                bg=bgj if include_artifact else None,
                enable=enj if include_artifact else None,
                art_modes=art_modes)
        return res_cs, plan, (payload, feats, diff)

    todo = [(res, cs) for res in range(len(K.RESOLUTION_SCALES))
            for cs in range(len(K.COLORSPACES))]
    lookahead = 2
    in_flight = [dispatch(rc) for rc in todo[:lookahead]]

    n_blur = len(K.BLUR_KERNELS)
    dets: dict[tuple[int, int, int, int], list[np.ndarray]] = {}
    feats_all: dict[tuple[int, int, int, int], np.ndarray] = {}
    cal_samples: list[tuple[int, int, int, np.ndarray, int]] = []
    plan_of_cs: dict[tuple[int, int], FK.TransformPlan] = {}

    for gi in range(len(todo)):
        (res, cs), plan, (payload, feats, diff) = in_flight[gi % lookahead]
        if gi + lookahead < len(todo):
            in_flight[gi % lookahead] = dispatch(todo[gi + lookahead])
        plan_of_cs[(res, cs)] = plan
        with TraceAnnotation("mez.char.wait"):
            diff_np = np.asarray(diff[:, :n_clip])       # [S, F, gh, gw]
            feats_np = np.asarray(feats[:, 1:n_real])    # [S, F, 6]
            s_dim, f_dim = diff_np.shape[:2]
            # only the calibration frame of each (blur, artifact) setting
            # ever needs its payload on the host -- slice on device, don't
            # ship the batch
            cal_idx = np.asarray([1 + (res * s_dim + b) % n_clip
                                  for b in range(s_dim)])
            cal_payloads = np.asarray(payload[jnp.arange(s_dim),
                                              jnp.asarray(cal_idx)])

        # adaptive threshold: detector.detect's own helper, batched, one
        # introselect pass for both quantiles (NumPy beats XLA's sort here)
        gh, gw = diff_np.shape[2:]
        with TraceAnnotation("mez.char.boxes"):
            eff = det.adaptive_threshold(
                diff_np.reshape(s_dim, f_dim, -1), detector_thresh, axis=-1)

        with TraceAnnotation("mez.char.label"):
            label_on_device = use_pallas
            if not label_on_device:
                try:
                    mask = det.dilate_cross(diff_np > eff[:, :, None, None])
                    ids, bg_label = _label_host(mask.reshape(-1, gh, gw))
                except ImportError:         # no scipy: device labeler works
                    label_on_device = True
            if label_on_device:
                ids = np.asarray(_label_group(jnp.asarray(diff_np),
                                              jnp.asarray(eff)))
                ids = ids.reshape(s_dim * f_dim, gh, gw)
                bg_label = gh * gw

        sy, sx = h / gh, w / gw
        min_px = max(2.0, min_area / (sy * sx))
        with TraceAnnotation("mez.char.boxes"):
            boxes = _segment_boxes_batch(ids, diff_np.reshape(-1, gh, gw),
                                         background_label=bg_label,
                                         sy=sy, sx=sx, min_px=min_px)
        with TraceAnnotation("mez.char.calib"):
            for s_i in range(s_dim):
                art, b = int(plan.art_ids[s_i]), s_i % n_blur
                combo = (res, cs, b, art)
                feats_all[combo] = feats_np[s_i]
                dets[combo] = boxes[s_i * f_dim:s_i * f_dim + n_clip]
                wire = _wire_payload(cal_payloads[s_i], cs)
                cal_samples.append((cs, art, plan.payload_bytes,
                                    feats_np[s_i, cal_idx[s_i] - 1],
                                    len(zlib.compress(wire.tobytes(), 1))))

    with TraceAnnotation("mez.char.score"):
        proxy = _fit_proxy(cal_samples)
        sizes = {
            (res, cs, b, art): proxy.predict(
                cs, plan_of_cs[(res, cs)].payload_bytes,
                feats_all[(res, cs, b, art)], art=art > 0)
            for (res, cs, b, art) in feats_all
        }
    return GridCharacterization(
        combos=tuple(sorted(feats_all)), dets=dets, sizes=sizes,
        change_counts=np.asarray(change_counts_dev), pixels=h * w,
        proxy=proxy, zlib_calls=len(cal_samples),
        include_artifact=include_artifact)


# =============================================================================
# Online re-characterization (live tables for the controller)
# =============================================================================


def refresh_tables(background: np.ndarray, frames: list[np.ndarray], *,
                   gts: list[np.ndarray] | None = None,
                   min_accuracy: float = 0.90,
                   include_artifact: bool = False,
                   detector_thresh: float = 28.0,
                   capacity: int | None = None):
    """Re-run the batched sweep over a LIVE clip and emit controller-ready
    tables: ``(CharacterizationTable, JaxControllerTables)``.

    This is the online (CANS-style) re-characterization entry point: the
    clip is whatever the camera recently published (``CamBroker`` feeds its
    log tail), and -- absent labels -- the full-quality combo's own
    detections act as pseudo-ground-truth, so accuracies are normalized F1
    against the unmodified stream, exactly the quantity the controller
    trades against latency.  Pass ``gts`` to score against real labels
    instead (the offline ``characterize`` path).

    ``capacity`` pads the device tables to a fixed row count so a jitted
    ``controller_step`` consumes refreshed tables with NO recompile (see
    ``controller.swap_tables``).
    """
    from repro.core import characterization as C
    from repro.core.controller import JaxControllerTables

    with TraceAnnotation("mez.char"):
        grid = run_grid(background, frames, detector_thresh=detector_thresh,
                        include_artifact=include_artifact)
        if gts is None:
            gts = grid.dets[(0, 0, 0, 0)]
        with TraceAnnotation("mez.char.score"):
            table = C.table_from_grid(grid, gts, min_accuracy=min_accuracy,
                                      include_artifact=include_artifact)
    # provenance: these tables were swept from live frames, not the
    # offline calibration campaign (drift tests / fig12 assert on this)
    table.source = "online-refresh"
    if capacity is not None:
        capacity = max(capacity, len(table.settings))
    return table, JaxControllerTables.from_table(table, capacity=capacity)
