"""Flash-decode: single-query attention against a long KV cache, Pallas TPU.

decode_32k / long_500k cells are HBM-bandwidth-bound: the step reads the
whole KV cache once and does O(S*D) FLOPs per head.  Grid = (batch, q_heads);
each program streams its KV-head's cache in [BK, D] tiles through VMEM,
carrying the online-softmax (m, l, acc) for its single query row.  Entries
past ``length`` are masked (the cache is preallocated with slack).

GQA mapping as in flash_attention: kv head = q head // group in index_map.
"""

# mezlint: ref-parity: repro.kernels.ref.decode_attention_ref

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

__all__ = ["decode_attention"]

NEG_INF = -2.3819763e38


def _decode_kernel(len_ref, q_ref, k_ref, v_ref, o_ref, *, scale: float,
                   block_k: int):
    q = q_ref[0, 0, 0, :].astype(jnp.float32) * scale          # [D]
    d = q.shape[0]
    length = len_ref[0]

    m0 = jnp.full((1,), NEG_INF, jnp.float32)
    l0 = jnp.zeros((1,), jnp.float32)
    acc0 = jnp.zeros((1, d), jnp.float32)

    def body(kv_i, carry):
        m, l, acc = carry
        k = k_ref[0, pl.ds(kv_i * block_k, block_k), 0,
                     :].astype(jnp.float32)   # [BK, D]
        v = v_ref[0, pl.ds(kv_i * block_k, block_k), 0,
                     :].astype(jnp.float32)
        s = (k @ q)[None, :]                                    # [1, BK]
        pos = kv_i * block_k + jax.lax.broadcasted_iota(
            jnp.int32, (1, block_k), 1)
        s = jnp.where(pos < length, s, NEG_INF)
        m_new = jnp.maximum(m, s.max(axis=-1))
        alpha = jnp.exp(m - m_new)
        p = jnp.exp(s - m_new[:, None])
        l_new = l * alpha + p.sum(axis=-1)
        acc_new = acc * alpha[:, None] + p @ v
        return m_new, l_new, acc_new

    n_kv = pl.cdiv(length, block_k)
    m, l, acc = jax.lax.fori_loop(0, n_kv, body, (m0, l0, acc0))
    out = acc / jnp.maximum(l, 1e-37)[:, None]
    o_ref[0, 0, 0, :] = out[0].astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("scale", "block_k", "interpret"))
def decode_attention(q: jax.Array, k_cache: jax.Array, v_cache: jax.Array,
                     length: jax.Array, *, scale: float | None = None,
                     block_k: int = 512, interpret: bool = False) -> jax.Array:
    """q: [B, 1, QH, D]; caches: [B, S_max, KH, D]; length: i32[] valid rows."""
    b, one, qh, d = q.shape
    assert one == 1
    _, smax, kh, _ = k_cache.shape
    group = qh // kh
    block_k = min(block_k, smax)
    assert smax % block_k == 0, (smax, block_k)
    scale = scale if scale is not None else d ** -0.5
    length = jnp.broadcast_to(jnp.asarray(length, jnp.int32), (1,))

    grid = (b, qh)
    return pl.pallas_call(
        functools.partial(_decode_kernel, scale=scale, block_k=block_k),
        grid=grid,
        in_specs=[
            pl.BlockSpec(memory_space=pl.ANY),   # length (scalar prefetchable)
            pl.BlockSpec((1, 1, 1, d), lambda bi, hi: (bi, 0, hi, 0)),
            pl.BlockSpec((1, smax, 1, d),
                         lambda bi, hi, group=group: (bi, 0, hi // group, 0)),
            pl.BlockSpec((1, smax, 1, d),
                         lambda bi, hi, group=group: (bi, 0, hi // group, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, 1, d), lambda bi, hi: (bi, 0, hi, 0)),
        out_shape=jax.ShapeDtypeStruct((b, 1, qh, d), q.dtype),
        interpret=interpret,
    )(length, q, k_cache, v_cache)
