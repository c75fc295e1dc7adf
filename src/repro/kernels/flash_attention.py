"""Blockwise (flash) causal GQA attention, Pallas TPU.

Grid = (batch, q_heads, S/BQ); each program owns one [BQ, D] query tile in
VMEM and streams the KV sequence in [BK, D] tiles, maintaining the online
softmax (m, l, acc) in VREGs/VMEM scratch.  Causal masking skips fully-masked
KV tiles via the fori upper bound (no wasted MXU work past the diagonal).
GQA: the q-head index maps to its KV head (kh = qh // group) in the
BlockSpec index_map, so KV tiles are fetched once per group.

Block shapes default to (BQ, BK) = (256, 512): MXU-aligned (multiples of
128) and a [BQ,D]+[2*BK,D]+[BQ,BK] working set well under VMEM at D<=256.
"""

# mezlint: ref-parity: repro.kernels.ref.flash_attention_ref

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

__all__ = ["flash_attention"]

NEG_INF = -2.3819763e38


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, *, scale: float, causal: bool,
                  block_q: int, block_k: int, seq_k: int):
    qi = pl.program_id(2)
    q = q_ref[0, :, 0, :].astype(jnp.float32) * scale          # [BQ, D]
    bq, d = q.shape

    m0 = jnp.full((bq,), NEG_INF, jnp.float32)
    l0 = jnp.zeros((bq,), jnp.float32)
    acc0 = jnp.zeros((bq, d), jnp.float32)

    q_pos = qi * block_q + jax.lax.broadcasted_iota(jnp.int32, (bq, block_k), 0)

    def body(kv_i, carry):
        m, l, acc = carry
        k = k_ref[0, pl.ds(kv_i * block_k, block_k), 0,
                     :].astype(jnp.float32)   # [BK, D]
        v = v_ref[0, pl.ds(kv_i * block_k, block_k), 0,
                     :].astype(jnp.float32)
        s = q @ k.T                                             # [BQ, BK]
        k_pos = kv_i * block_k + jax.lax.broadcasted_iota(
            jnp.int32, (bq, block_k), 1)
        mask = k_pos < seq_k
        if causal:
            mask = mask & (q_pos >= k_pos)
        s = jnp.where(mask, s, NEG_INF)
        m_new = jnp.maximum(m, s.max(axis=-1))
        alpha = jnp.exp(m - m_new)
        p = jnp.exp(s - m_new[:, None])
        l_new = l * alpha + p.sum(axis=-1)
        acc_new = acc * alpha[:, None] + p @ v
        return m_new, l_new, acc_new

    if causal:
        # last KV tile that intersects the causal frontier of this q tile
        hi = (qi + 1) * block_q
        n_kv = pl.cdiv(jnp.minimum(hi, seq_k), block_k)
    else:
        n_kv = pl.cdiv(seq_k, block_k)
    m, l, acc = jax.lax.fori_loop(0, n_kv, body, (m0, l0, acc0))
    out = acc / jnp.maximum(l, 1e-37)[:, None]
    o_ref[0, :, 0, :] = out.astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("causal", "scale", "block_q",
                                             "block_k", "interpret"))
def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                    causal: bool = True, scale: float | None = None,
                    block_q: int = 256, block_k: int = 512,
                    interpret: bool = False) -> jax.Array:
    """q: [B, Sq, QH, D]; k/v: [B, Sk, KH, D] (QH % KH == 0)."""
    b, sq, qh, d = q.shape
    _, sk, kh, _ = k.shape
    group = qh // kh
    block_q = min(block_q, sq)
    block_k = min(block_k, sk)
    pad_q = (-sq) % block_q
    pad_k = (-sk) % block_k
    if pad_q:
        q = jnp.pad(q, ((0, 0), (0, pad_q), (0, 0), (0, 0)))
    if pad_k:
        k = jnp.pad(k, ((0, 0), (0, pad_k), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad_k), (0, 0), (0, 0)))
    sq_p, sk_p = sq + pad_q, sk + pad_k
    scale = scale if scale is not None else d ** -0.5

    grid = (b, qh, sq_p // block_q)
    out = pl.pallas_call(
        functools.partial(_flash_kernel, scale=scale, causal=causal,
                          block_q=block_q, block_k=block_k, seq_k=sk),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_q, 1, d), lambda bi, hi, qi: (bi, qi, hi, 0)),
            pl.BlockSpec((1, sk_p, 1, d),
                         lambda bi, hi, qi, group=group: (bi, 0, hi // group, 0)),
            pl.BlockSpec((1, sk_p, 1, d),
                         lambda bi, hi, qi, group=group: (bi, 0, hi // group, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_q, 1, d),
                               lambda bi, hi, qi: (bi, qi, hi, 0)),
        out_shape=jax.ShapeDtypeStruct((b, sq_p, qh, d), q.dtype),
        interpret=interpret,
    )(q, k, v)
    return out[:, :sq]
