"""Gated linear recurrence (RWKV6 wkv) Pallas kernel.

    y_t     = r_t . (state_{t-1} + diag(u) k_t v_t^T)
    state_t = diag(w_t) state_{t-1} + k_t v_t^T          state: [K, V]

Grid = (batch, heads); each program owns its head's [K, V] state in a VMEM
scratch accumulator (fp32) and walks the sequence in chunks of BT steps.
Within a chunk the cross-term is an exact [BT, BT] decay-weighted matmul
(all exponents <= 0 -- numerically safe), so the MXU does the heavy lifting
and the serial dependency only crosses chunk boundaries.  This is the TPU
adaptation of the RWKV CUDA kernel: instead of one-thread-per-channel serial
scans, chunk-parallel matmuls + a carried VMEM state.
"""

# mezlint: ref-parity: repro.kernels.ref.wkv_ref

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["wkv_linear_scan"]


def _wkv_kernel(r_ref, k_ref, v_ref, lw_ref, u_ref, y_ref, s_out_ref,
                state_ref, *, block_t: int, seq: int):
    kd = r_ref.shape[-1]
    state_ref[...] = jnp.zeros((kd, kd), jnp.float32)
    n_chunks = seq // block_t
    # strict lower-triangular mask: s < t
    mask = (jax.lax.broadcasted_iota(jnp.int32, (block_t, block_t), 0)
            > jax.lax.broadcasted_iota(jnp.int32, (block_t, block_t), 1))
    u = u_ref[0, :].astype(jnp.float32)

    def chunk(ci, _):
        sl = (0, pl.ds(ci * block_t, block_t), 0, slice(None))
        r = r_ref[sl].astype(jnp.float32)            # [BT, K] (ints squeeze)
        k = k_ref[sl].astype(jnp.float32)
        v = v_ref[sl].astype(jnp.float32)
        lw = lw_ref[sl].astype(jnp.float32)

        cum = jnp.cumsum(lw, axis=0)                              # [BT, K]
        cum_tm1 = cum - lw
        state = state_ref[...]

        # incoming-state + diagonal bonus terms
        y = ((r * jnp.exp(cum_tm1)) @ state
             + jnp.einsum("tk,tk,tv->tv", r * u, k, v))
        # intra-chunk cross terms, exact per-channel decay:
        #   att[t,s] = sum_k r[t,k] k[s,k] exp(cum_{t-1}[t,k] - cum[s,k]), s<t
        att = jnp.einsum("tk,sk,tsk->ts", r, k,
                         jnp.exp(cum_tm1[:, None, :] - cum[None, :, :]))
        att = jnp.where(mask, att, 0.0)
        y = y + att @ v
        y_ref[sl] = y.astype(y_ref.dtype)

        # state update: state = diag(exp(cum_end)) state + sum_s dec_s k_s v_s^T
        dec_end = jnp.exp(cum[-1][None, :] - cum)                 # [BT, K]
        state_ref[...] = (jnp.exp(cum[-1])[:, None] * state
                          + (k * dec_end).T @ v)
        return 0

    jax.lax.fori_loop(0, n_chunks, chunk, 0)
    s_out_ref[0, 0, :, :] = state_ref[...]


@functools.partial(jax.jit, static_argnames=("block_t", "interpret"))
def wkv_linear_scan(r: jax.Array, k: jax.Array, v: jax.Array,
                    logw: jax.Array, u: jax.Array, *, block_t: int = 64,
                    interpret: bool = False) -> tuple[jax.Array, jax.Array]:
    """r/k/v/logw: [B, S, H, K]; u: [H, K] -> (y [B,S,H,K], state [B,H,K,K])."""
    b, s, h, kd = r.shape
    block_t = min(block_t, s)
    assert s % block_t == 0, (s, block_t)
    grid = (b, h)
    y, state = pl.pallas_call(
        functools.partial(_wkv_kernel, block_t=block_t, seq=s),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, s, 1, kd), lambda bi, hi: (bi, 0, hi, 0)),
            pl.BlockSpec((1, s, 1, kd), lambda bi, hi: (bi, 0, hi, 0)),
            pl.BlockSpec((1, s, 1, kd), lambda bi, hi: (bi, 0, hi, 0)),
            pl.BlockSpec((1, s, 1, kd), lambda bi, hi: (bi, 0, hi, 0)),
            pl.BlockSpec((1, kd), lambda bi, hi: (hi, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, s, 1, kd), lambda bi, hi: (bi, 0, hi, 0)),
            pl.BlockSpec((1, 1, kd, kd), lambda bi, hi: (bi, hi, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, s, h, kd), r.dtype),
            jax.ShapeDtypeStruct((b, h, kd, kd), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((kd, kd), jnp.float32)],
        interpret=interpret,
    )(r, k, v, logw, u)
    return y, state
