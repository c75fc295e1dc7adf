"""Pure-jnp oracles for every Pallas kernel (the correctness contracts).

Each ref implements the kernel's EXACT semantics (including block-local
behaviour where the kernel is blockwise by design) so tests can
assert_allclose across shape/dtype sweeps.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

__all__ = ["quantize_ref", "dequantize_ref", "flash_attention_ref",
           "decode_attention_ref", "wkv_ref", "frame_knobs_ref",
           "frame_knob_grid_ref"]


# -----------------------------------------------------------------------------
# quantize
# -----------------------------------------------------------------------------


def quantize_ref(x: jax.Array, *, block=(256, 512), bits: int = 8):
    m, n = x.shape
    bm, bn = min(block[0], m), min(block[1], n)
    qmax = {8: 127.0, 4: 7.0}[bits]
    xb = x.astype(jnp.float32).reshape(m // bm, bm, n // bn, bn)
    xb = xb.transpose(0, 2, 1, 3)                     # [GM, GN, bm, bn]
    absmax = jnp.max(jnp.abs(xb), axis=(-1, -2))
    scales = jnp.maximum(absmax / qmax, 1e-12)
    q = jnp.clip(jnp.round(xb / scales[..., None, None]), -qmax, qmax)
    q = q.transpose(0, 2, 1, 3).reshape(m, n).astype(jnp.int8)
    return q, scales


def dequantize_ref(q: jax.Array, scales: jax.Array, *, block=(256, 512),
                   out_dtype=jnp.float32):
    m, n = q.shape
    bm, bn = min(block[0], m), min(block[1], n)
    qb = q.astype(jnp.float32).reshape(m // bm, bm, n // bn, bn)
    qb = qb.transpose(0, 2, 1, 3) * scales[..., None, None]
    return qb.transpose(0, 2, 1, 3).reshape(m, n).astype(out_dtype)


# -----------------------------------------------------------------------------
# attention
# -----------------------------------------------------------------------------


def flash_attention_ref(q, k, v, *, causal=True, scale=None):
    """Reference = exact softmax attention (GQA-expanded inputs)."""
    scale = scale if scale is not None else q.shape[-1] ** -0.5
    s = jnp.einsum("bqhd,bkhd->bhqk", q.astype(jnp.float32),
                   k.astype(jnp.float32)) * scale
    if causal:
        sq, sk = q.shape[1], k.shape[1]
        mask = jnp.arange(sq)[:, None] >= jnp.arange(sk)[None, :]
        s = jnp.where(mask[None, None], s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", p,
                      v.astype(jnp.float32)).astype(q.dtype)


def decode_attention_ref(q, k_cache, v_cache, length, *, scale=None):
    scale = scale if scale is not None else q.shape[-1] ** -0.5
    s = jnp.einsum("bqhd,bkhd->bhqk", q.astype(jnp.float32),
                   k_cache.astype(jnp.float32)) * scale
    smax = k_cache.shape[1]
    valid = jnp.arange(smax)[None, :] < jnp.reshape(length, (-1, 1))
    s = jnp.where(valid[:, None, None, :], s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", p,
                      v_cache.astype(jnp.float32)).astype(q.dtype)


# -----------------------------------------------------------------------------
# gated linear recurrence (rwkv6 wkv)
# -----------------------------------------------------------------------------


def wkv_ref(r, k, v, logw, u, *, state0=None):
    """Step-by-step recurrence.  r/k/v/logw: [B,S,H,K]; u: [H,K].

        y_t     = r_t . (state_{t-1} + diag(u) k_t v_t^T)
        state_t = diag(w_t) state_{t-1} + k_t v_t^T
    """
    b, s, h, kd = r.shape
    r32, k32, v32 = (x.astype(jnp.float32) for x in (r, k, v))
    w = jnp.exp(logw.astype(jnp.float32))

    def step(state, xs):
        rt, kt, vt, wt = xs                       # [B,H,K]
        kv = kt[..., :, None] * vt[..., None, :]  # [B,H,K,V]
        y = jnp.einsum("bhk,bhkv->bhv", rt,
                       state + u[None, :, :, None] * kv)
        state = wt[..., None] * state + kv
        return state, y

    if state0 is None:
        state0 = jnp.zeros((b, h, kd, kd), jnp.float32)
    xs = tuple(x.transpose(1, 0, 2, 3) for x in (r32, k32, v32, w))
    state, ys = jax.lax.scan(step, state0, xs)
    return ys.transpose(1, 0, 2, 3).astype(r.dtype), state


# -----------------------------------------------------------------------------
# frame knobs (fused downsample + blur + change metric)
# -----------------------------------------------------------------------------


def frame_knobs_ref(frames: jax.Array, prev: jax.Array, *, blur_k: int = 5,
                    pixel_delta: float = 8.0):
    """Per-frame: 2x2 mean-pool -> block-local box blur (edge-clamped) ->
    fraction of changed pixels vs ``prev`` (pre-downsample).

    frames/prev: [N, H, W] float32 or uint8.  Returns (out [N,H/2,W/2] f32,
    changed_frac [N] f32).  Semantics match the Pallas kernel exactly
    (whole-frame blocks, edge-clamped blur).
    """
    f = frames.astype(jnp.float32)
    p = prev.astype(jnp.float32)
    changed = (jnp.abs(f - p) > pixel_delta).mean(axis=(1, 2))
    n, h, w = f.shape
    pooled = f.reshape(n, h // 2, 2, w // 2, 2).mean(axis=(2, 4))
    if blur_k > 1:
        pad = blur_k // 2
        padded = jnp.pad(pooled, ((0, 0), (pad, blur_k - 1 - pad), (0, 0)),
                         mode="edge")
        kern = jnp.ones((blur_k,), jnp.float32) / blur_k
        pooled = jax.vmap(
            lambda img: jax.vmap(lambda col: jnp.convolve(col, kern, mode="valid"),
                                 in_axes=1, out_axes=1)(img))(padded)
        padded = jnp.pad(pooled, ((0, 0), (0, 0), (pad, blur_k - 1 - pad)),
                         mode="edge")
        pooled = jax.vmap(
            lambda img: jax.vmap(lambda row: jnp.convolve(row, kern, mode="valid"))(img))(padded)
    return pooled, changed


# -----------------------------------------------------------------------------
# generalized knob grid (colorspace + arbitrary resize + blur + proxy feats)
# -----------------------------------------------------------------------------


def frame_knob_grid_ref(frames: jax.Array, prev: jax.Array, plan, *,
                        background: jax.Array | None = None,
                        art_enable: jax.Array | None = None,
                        pixel_delta: float = 8.0,
                        art_thresh: float | None = None):
    """Oracle for ``frame_knobs.frame_knob_grid``: one (setting, frame)
    program at a time via ``lax.map``, so every contraction runs at the
    exact per-program shapes of the Pallas grid -- bit-exact including the
    uint8 round/clip after each stage.

    frames/prev: uint8 [F, H, W, 3].  Plans batching knob4 settings need
    ``background`` (and optionally ``art_enable`` [F], default all-on),
    mirroring the kernel's inputs.  Returns (payload [S, F, P, oh, ow]
    uint8, feats [S, F, 6] f32, changed [S, F] f32).
    """
    from repro.kernels.frame_knobs import (ARTIFACT_THRESH, _grid_compute,
                                           _split_stats, exact_operators)

    if art_thresh is None:
        art_thresh = ARTIFACT_THRESH
    s = plan.bys.shape[0]
    f = frames.shape[0]
    ry, rx, bys, bxs = map(jnp.asarray, exact_operators(plan))
    frames = jnp.transpose(frames, (0, 3, 1, 2))          # planes-first
    prev = jnp.transpose(prev, (0, 3, 1, 2))
    with_art = background is not None
    if plan.with_artifact and not with_art:
        raise ValueError("plan batches knob4 settings; pass background=")
    if with_art:
        bg = jnp.transpose(jnp.asarray(background), (2, 0, 1))
        art_ids = jnp.asarray(plan.art_ids)
        enable = (jnp.ones((f,), jnp.int32) if art_enable is None
                  else jnp.asarray(art_enable, jnp.int32))

    def one(idx):
        si, fi = idx // f, idx % f
        kwargs = {}
        if with_art:
            kwargs = dict(bg=bg, art_mode=art_ids[si] * enable[fi],
                          art_thresh=art_thresh)
        payload, stats = _grid_compute(
            frames[fi], prev[fi], ry, rx, bys[si], bxs[si], cs=plan.cs,
            pixel_delta=pixel_delta, **kwargs)
        return jnp.stack(payload), stats

    payload, stats = jax.lax.map(one, jnp.arange(s * f))
    return _split_stats(
        payload.reshape(s, f, plan.n_planes, plan.out_h, plan.out_w),
        stats.reshape(s, f, 1, -1), plan.in_h * plan.in_w)
