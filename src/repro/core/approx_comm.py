"""Approximate collectives: Algorithm 1 pointed at the cross-pod link.

The paper trades video-frame fidelity for wireless latency under an accuracy
floor.  At pod scale the contended, variable-latency link is the cross-pod
gradient reduction (DCN between pods is ~10x slower than intra-pod ICI and
shared with other jobs).  This module applies the SAME control law:

  payload knob     gradient quantization level: bf16 -> int8 -> int4-range
                   (repro.kernels.quantize, per-block symmetric scales)
  latency sensor   measured collective time per step
  regression       latency ~= slope * payload_bytes + intercept (links are
                   bandwidth-dominated, same linearity the paper exploits)
  accuracy floor   gradient fidelity = cosine similarity between the
                   compressed-reduced gradient and the exact one,
                   characterized offline per level (the paper's size ->
                   accuracy table, with cosine fidelity in place of F1)
  controller       repro.core.controller.controller_step (the jittable PI
                   controller) picks the level each step

The collective itself: each pod quantizes its pod-mean gradient, all-gathers
the int8 payload + fp32 block scales over the pod axis, and locally
dequantize-averages (sum_i q_i * s_i / N).  Exact semantics at a quarter of
the wire bytes (int8) -- and unlike DIY psum-of-int8, per-shard scales stay
correct.  Runs inside shard_map over the 'pod' axis.

``make_grad_compressor`` returns the hook `steps.build_train_step` accepts.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Callable

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import ref as kref

__all__ = ["CompressionLevel", "LEVELS", "compressed_mean",
           "make_grad_compressor", "characterize_fidelity",
           "collective_bytes_for", "fidelity_table", "CollectiveController"]


@dataclasses.dataclass(frozen=True)
class CompressionLevel:
    name: str
    bits: int            # 16 = no compression, 8, 4
    wire_factor: float   # payload bytes / bf16 bytes


LEVELS = (
    CompressionLevel("bf16", 16, 1.0),
    CompressionLevel("int8", 8, 0.5 + 1 / 256),     # + per-block scales
    CompressionLevel("int4", 4, 0.25 + 1 / 256),
)


def _pad_2d(x: jax.Array, block=(256, 512)) -> tuple[jax.Array, tuple]:
    flat = x.reshape(-1)
    n = flat.shape[0]
    bn = block[0] * block[1]
    pad = (-n) % bn
    if pad:
        flat = jnp.pad(flat, (0, pad))
    rows = flat.shape[0] // block[1]
    return flat.reshape(rows, block[1]), (n,)


def _quant_roundtrip(x: jax.Array, bits: int, block=(256, 512)) -> jax.Array:
    """Quantize-dequantize a tensor (the numerical effect of transport)."""
    if bits >= 16:
        return x
    x2d, (n,) = _pad_2d(x, block)
    q, s = kref.quantize_ref(x2d, block=block, bits=bits)
    xd = kref.dequantize_ref(q, s, block=block, out_dtype=jnp.float32)
    return xd.reshape(-1)[:n].reshape(x.shape).astype(x.dtype)


# mezlint: jit-entry
def compressed_mean(x: jax.Array, axis_name: str, bits: int,
                    block=(256, 512)) -> jax.Array:
    """Mean over ``axis_name`` with quantized transport (inside shard_map).

    all-gather int8 payloads + scales, dequantize-average locally; bits>=16
    falls back to the exact psum-mean.
    """
    n_dev = jax.lax.axis_size(axis_name)
    if bits >= 16:
        return jax.lax.pmean(x, axis_name)
    x2d, (n,) = _pad_2d(x, block)
    q, s = kref.quantize_ref(x2d, block=block, bits=bits)
    qg = jax.lax.all_gather(q, axis_name)          # [N, rows, bn] int8
    sg = jax.lax.all_gather(s, axis_name)          # [N, gr, gc] f32
    xg = jax.vmap(lambda qq, ss: kref.dequantize_ref(qq, ss, block=block))(
        qg, sg)
    mean = xg.sum(axis=0) / n_dev
    return mean.reshape(-1)[:n].reshape(x.shape).astype(x.dtype)


def make_grad_compressor(bits: int, *, block=(256, 512),
                         min_size: int = 65536) -> Callable:
    """Hook for build_train_step: models cross-pod transport compression.

    Under GSPMD the cross-pod reduction is implicit in the gradient psum, so
    the hook applies the quantization ROUND-TRIP to every large gradient leaf
    -- the numerics of compressed transport -- while the §Roofline collective
    accounting applies the wire factor to the cross-pod byte term.  (The
    explicit shard_map collective lives in ``compressed_mean`` and is used
    by the approx-comm example/benchmark where the pod axis is real.)
    """
    def hook(grads):
        if bits >= 16:
            return grads
        return jax.tree_util.tree_map(
            lambda g: _quant_roundtrip(g, bits, block)
            if g.size >= min_size else g, grads)
    return hook


def collective_bytes_for(grad_bytes_bf16: float, bits: int) -> float:
    lvl = {l.bits: l for l in LEVELS}[bits]
    return grad_bytes_bf16 * lvl.wire_factor


def fidelity_table(grad_bytes_bf16: float, fidelity: dict[int, float]):
    """The Algorithm-1 tables for the cross-pod link: "size" = wire bytes
    per compression level, "accuracy" = gradient cosine fidelity (the F1
    analogue).  Returns a ``CharacterizationTable`` ready for either the
    host ``LatencyController`` or the jitted ``controller_step`` path."""
    from repro.core.characterization import CharacterizationTable
    from repro.core.knobs import KnobSetting

    sizes = np.asarray([collective_bytes_for(grad_bytes_bf16, lvl.bits)
                        for lvl in LEVELS], np.float64)
    accs = np.asarray([fidelity[lvl.bits] for lvl in LEVELS], np.float64)
    order = np.argsort(sizes, kind="stable")
    best_acc, best_idx, run = [], [], (-1.0, -1)
    for i in order:
        if accs[i] > run[0]:
            run = (float(accs[i]), int(i))
        best_acc.append(run[0])
        best_idx.append(run[1])
    return CharacterizationTable(
        settings=tuple(KnobSetting() for _ in LEVELS),
        sizes_sorted=sizes[order], best_acc=np.asarray(best_acc),
        best_idx=np.asarray(best_idx), acc_by_setting=accs,
        size_by_setting=sizes, min_accuracy=0.0, source="approx-comm")


@dataclasses.dataclass(frozen=True)
class CollectiveDecision:
    """One reduction's transport decision."""
    bits: int                # compression level to use for the NEXT step
    setting_index: int       # row of the fidelity table (-1 = none)
    feasible: bool           # fidelity floor met within the latency budget
    acted: bool              # outside the error band this step


class CollectiveController:
    """Algorithm 1 picking the gradient compression level, on the JITTED
    controller path (ROADMAP PR 4 follow-up: drive ``approx_comm``'s knob
    from fleet decisions).

    A one-lane fleet: the fidelity table becomes capacity-padded
    ``JaxControllerTables``, the law constants become a stacked
    ``ControllerParams`` row (gains precomputed in float64, exactly the
    host contract), and every reduction steps ``fleet_controller_step`` --
    the SAME compiled vmapped core the camera fleet runs, pointed at the
    cross-pod link.  Decisions are therefore bit-identical to a host
    ``LatencyController`` with the same config (asserted by
    tests/test_runtime.py), and the controller can later join a real
    multi-lane fleet (cameras and collectives in one dispatch) without
    changing semantics.
    """

    def __init__(self, grad_bytes_bf16: float, fidelity: dict[int, float],
                 *, latency_target: float, fidelity_floor: float = 0.98,
                 slope: float, intercept: float = 1e-4,
                 error_threshold: float | None = None,
                 capacity: int | None = None):
        from repro.core.characterization import LatencyRegression
        from repro.core.controller import (ControllerConfig,
                                           JaxControllerTables,
                                           LatencyController,
                                           fleet_controller_init,
                                           fleet_controller_step,
                                           stack_params, stack_tables,
                                           ControllerParams)
        self.table = fidelity_table(grad_bytes_bf16, fidelity)
        if error_threshold is None:
            error_threshold = 0.05 * latency_target
        cfg = ControllerConfig(latency_target=latency_target,
                               accuracy_target=fidelity_floor,
                               error_threshold=error_threshold)
        reg = LatencyRegression(slope=slope, intercept=intercept)
        # the host twin seeds the operating point (nominal-size row) and
        # supplies the float64-precomputed gains -- the parity contract
        self._host = LatencyController(cfg, self.table, reg)
        cap = capacity or max(8, len(LEVELS))
        self.tables = stack_tables(
            [JaxControllerTables.from_table(self.table, capacity=cap)])
        self.params = stack_params(
            [ControllerParams.from_controller(self._host)])
        self.state = fleet_controller_init(
            self.tables, start_idx=np.asarray([self._host._current],
                                              np.int32))
        self._step = jax.jit(
            lambda st, lat, tb, pr: fleet_controller_step(st, lat, tb, pr))
        self.bits = LEVELS[self._host._current].bits

    def cache_size(self) -> int:
        """Compiled-variant count of the decision step (1 = no retraces)."""
        return self._step._cache_size()

    def update(self, latency_sampled: float) -> CollectiveDecision:
        """One control tick: feed the measured reduction latency, get the
        compression level for the next step (ONE compiled dispatch)."""
        self.state, aux = self._step(
            self.state, jnp.asarray([latency_sampled], jnp.float32),
            self.tables, self.params)
        a = jax.device_get(aux)
        idx = int(a.idx[0])
        if idx >= 0:
            self.bits = LEVELS[idx].bits
        return CollectiveDecision(bits=self.bits, setting_index=idx,
                                  feasible=bool(a.feasible[0]),
                                  acted=bool(a.acted[0]))


def characterize_fidelity(grads_sample, *, block=(256, 512)) -> dict[int, float]:
    """Offline size->accuracy table (paper Section 2.4 analogue): cosine
    similarity between round-tripped and exact gradients, per level."""
    flat, _ = jax.tree_util.tree_flatten(grads_sample)
    vec = jnp.concatenate([x.reshape(-1).astype(jnp.float32) for x in flat])
    out = {}
    for lvl in LEVELS:
        if lvl.bits >= 16:
            out[lvl.bits] = 1.0
            continue
        rts = [_quant_roundtrip(x.astype(jnp.float32), lvl.bits, block)
               for x in flat]
        rvec = jnp.concatenate([x.reshape(-1) for x in rts])
        cos = jnp.vdot(vec, rvec) / (
            jnp.linalg.norm(vec) * jnp.linalg.norm(rvec) + 1e-12)
        out[lvl.bits] = float(cos)
    return out
