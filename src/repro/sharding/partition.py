"""Logical-axis sharding rules -> PartitionSpecs for params, batches, caches.

Mesh axes:
  pod    (multi-pod only)  composes with `data` into the DP/FSDP axis
  data   DP: batch dims; FSDP: the d_model-ish dim of every weight
  model  TP: heads / d_ff / vocab / experts; SP: decode KV sequence

Rules are name-based over the parameter pytree (tree_map_with_path); every
family's parameter names were chosen so the table below covers them:

  name                      layout                     spec (L = scan dim)
  embed                     [V, D]                     (model, fsdp)*
  lm_head                   [D, V]                     (fsdp, model)*
  wq|wk|wv|wg|wr|w_gate|w_up|cm_wk|cm_wr|in_proj|mix_down|w_down(lora)
                            [L, D, out]                (None, fsdp, model)
  wo|w_down|cm_wv|out_proj  [L, in, D]                 (None, model, fsdp)
  moe router                [L, D, E]                  (None, fsdp, None)
  moe w_gate|w_up           [L, E, D, F]   EP          (None, model, fsdp, None)
  moe w_down                [L, E, F, D]   EP          (None, model, None, fsdp)
  conv_w                    [L, K, C]                  (None, None, model)
  lora qa|ka|va             [I, D, r]                  (None, fsdp, None)
  lora qb|kb|vb             [I, r, out]                (None, None, model)
  norms / scalars           replicated

  (*) vocab falls back to replicated when V % model != 0 (seamless's 256206).

Every rule checks divisibility and drops the axis if it doesn't divide --
sharding must never change numerics or fail to lower.
"""

from __future__ import annotations

import re
from typing import Any

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.configs.base import ModelConfig, ShapeCell

__all__ = ["param_specs", "batch_specs", "cache_specs", "fsdp_axes",
           "shardings_for", "opt_state_specs", "logical_to_sharding",
           "fleet_mesh", "padded_lane_count", "shard_fleet_tick",
           "fleet_sharding"]


def fsdp_axes(mesh: Mesh, cfg: ModelConfig):
    """The DP/FSDP axis (composes pod+data on multi-pod meshes; zero3 mode
    folds the model axis in too)."""
    names = ("pod", "data", "model") if getattr(cfg, "zero3", False) \
        else ("pod", "data")
    return tuple(n for n in names if n in mesh.axis_names)


def _dp(mesh: Mesh, cfg: ModelConfig | None = None):
    names = ("pod", "data", "model") if (cfg is not None and
                                         getattr(cfg, "zero3", False)) \
        else ("pod", "data")
    return tuple(n for n in names if n in mesh.axis_names)


def _size(mesh: Mesh, axes) -> int:
    if axes is None:
        return 1
    if isinstance(axes, str):
        axes = (axes,)
    return int(np.prod([mesh.shape[a] for a in axes]))


def _maybe(mesh: Mesh, dim: int, axes):
    """axes if they divide dim, else None (replicate)."""
    if axes is None:
        return None
    sz = _size(mesh, axes)
    return axes if (sz > 0 and dim % sz == 0) else None


def _best_prefix(mesh: Mesh, dim: int, axes):
    """Longest prefix of ``axes`` whose size divides dim (zero3 multi-pod:
    batch 256 can't shard 512 ways -- fall back to (pod, data))."""
    if axes is None:
        return None
    if isinstance(axes, str):
        axes = (axes,)
    for k in range(len(axes), 0, -1):
        sub = axes[:k]
        if dim % _size(mesh, sub) == 0:
            return sub
    return None


# -----------------------------------------------------------------------------
# parameters
# -----------------------------------------------------------------------------

# leaf-name -> (in_axis_role, out_axis_role); roles: fsdp | model | none
_COL_PARALLEL = re.compile(
    r"^(wq|wk|wv|wg|wr|w_gate|w_up|cm_wk|cm_wr|in_proj|mix_down|w_down_lora)$")
_ROW_PARALLEL = re.compile(r"^(wo|w_down|cm_wv|out_proj)$")


def _leaf_name(path) -> str:
    for entry in reversed(path):
        if isinstance(entry, jax.tree_util.DictKey):
            return str(entry.key)
    return ""


def _path_names(path) -> list[str]:
    return [str(e.key) for e in path if isinstance(e, jax.tree_util.DictKey)]


def param_specs(params_shape: Any, cfg: ModelConfig, mesh: Mesh):
    """PartitionSpec pytree matching ``jax.eval_shape`` of the params."""
    fsdp = fsdp_axes(mesh, cfg) if cfg.fsdp else None
    model = "model" if "model" in mesh.axis_names else None

    if getattr(cfg, "zero3", False):
        model = None                      # no tensor parallelism

    def spec(path, leaf) -> P:
        names = _path_names(path)
        name = _leaf_name(path)
        shape = leaf.shape
        nd = len(shape)
        in_moe = "moe" in names
        # --- embeddings ------------------------------------------------------
        if name == "embed":
            v, d = shape
            return P(_maybe(mesh, v, model), _maybe(mesh, d, fsdp))
        if name == "lm_head":
            d, v = shape
            return P(_maybe(mesh, d, fsdp), _maybe(mesh, v, model))
        # --- MoE expert weights [L, E, D, F] / [L, E, F, D] -------------------
        if in_moe and name in ("w_gate", "w_up", "w_down") and nd == 4:
            L, e, a, b = shape
            if cfg.moe_parallel == "ep":
                espec = _maybe(mesh, e, model)
                if name == "w_down":    # [L, E, F, D]
                    return P(None, espec, None, _maybe(mesh, b, fsdp))
                return P(None, espec, _maybe(mesh, a, fsdp), None)
            else:                        # TP inside experts
                if name == "w_down":    # [L, E, F, D]
                    return P(None, None, _maybe(mesh, a, model),
                             _maybe(mesh, b, fsdp))
                return P(None, None, _maybe(mesh, a, fsdp),
                         _maybe(mesh, b, model))
        if in_moe and name == "router":  # [L, D, E]
            return P(None, _maybe(mesh, shape[1], fsdp), None)
        # --- zamba LoRA stacks [I, D, r] / [I, r, out] ------------------------
        if name in ("qa", "ka", "va"):
            return P(None, _maybe(mesh, shape[1], fsdp), None)
        if name in ("qb", "kb", "vb"):
            return P(None, None, _maybe(mesh, shape[2], model))
        # --- mamba conv [L, K, C] ---------------------------------------------
        if name == "conv_w":
            return P(*([None] * (nd - 1)), _maybe(mesh, shape[-1], model))
        # --- generic col/row parallel (leading scan dims allowed) -------------
        # Under sequence parallelism, attention weights drop the model axis
        # ONLY when the head count doesn't divide it (phi3: 40H vs 16) --
        # that's the case where head-sharding computes redundantly.  Archs
        # with divisible heads (llama3: 32H) keep Megatron-TP weights and
        # get RS/AG'd boundary activations instead.
        attn_names = ("wq", "wk", "wv", "wo")
        msize = _size(mesh, model)
        sp_attn = (cfg.sequence_parallel and name in attn_names
                   and cfg.num_heads % max(msize, 1) != 0)
        if _COL_PARALLEL.match(name) and nd >= 2:
            lead = [None] * (nd - 2)
            return P(*lead, _maybe(mesh, shape[-2], fsdp),
                     None if sp_attn else _maybe(mesh, shape[-1], model))
        if _ROW_PARALLEL.match(name) and nd >= 2:
            lead = [None] * (nd - 2)
            return P(*lead, None if sp_attn else _maybe(mesh, shape[-2], model),
                     _maybe(mesh, shape[-1], fsdp))
        if name in ("w_down",) and nd >= 2:  # non-moe fallthrough safety
            lead = [None] * (nd - 2)
            return P(*lead, _maybe(mesh, shape[-2], model),
                     _maybe(mesh, shape[-1], fsdp))
        if name in ("w_up", "mix_up") and nd >= 2:
            lead = [None] * (nd - 2)
            return P(*lead, _maybe(mesh, shape[-2], None),
                     _maybe(mesh, shape[-1], model))
        # --- everything else (norms, scalars, biases, u, A_log, ...) ----------
        return P()

    return jax.tree_util.tree_map_with_path(spec, params_shape)


def opt_state_specs(params_shape: Any, cfg: ModelConfig, mesh: Mesh):
    """Adam m/v mirror the parameter sharding; counters replicated."""
    pspecs = param_specs(params_shape, cfg, mesh)
    return {"m": pspecs, "v": pspecs, "count": P()}


# -----------------------------------------------------------------------------
# batches and caches
# -----------------------------------------------------------------------------


def batch_specs(batch_shape: dict, cfg: ModelConfig, mesh: Mesh,
                cell: ShapeCell):
    dp = _dp(mesh, cfg)

    def spec(path, leaf):
        b = leaf.shape[0]
        lead = _best_prefix(mesh, b, dp)
        # shard only the batch dim; seq/feature replicated for activations
        return P(lead, *([None] * (len(leaf.shape) - 1)))

    return jax.tree_util.tree_map_with_path(spec, batch_shape)


def cache_specs(cache_shape: Any, cfg: ModelConfig, mesh: Mesh,
                cell: ShapeCell):
    """KV/state cache sharding for decode cells.

    Layouts handled:
      [L, B, S, KH, HD]  kv cache      -> B: dp, S: model  (flash-decode SP)
      [B, S, D]          enc_out       -> B: dp
      [L, B, H, K, V]    wkv/ssm state -> B: dp, H: model
      [L, B, K-1, C]     conv state    -> B: dp, C: model
      [L, B, D]          shift state   -> B: dp
      scalars            replicated

    When B < dp size (long_500k has B=1), B falls back to replicated and the
    big sequence dim picks up (data, model) combined.
    """
    dp = _dp(mesh, cfg)
    model = ("model" if "model" in mesh.axis_names
             and not getattr(cfg, "zero3", False) else None)

    def spec(path, leaf):
        shape = leaf.shape
        nd = len(shape)
        if nd == 0 or max(shape) == 1 and nd <= 1:
            return P()
        name = _leaf_name(path)
        if nd == 5:   # [L, B, S, KH, HD] kv cache or [L, B, H, K, V] state
            L, b, s, h, d = shape
            bspec = _maybe(mesh, b, dp)
            if name in ("k", "v", "self_k", "self_v", "cross_k", "cross_v",
                        "attn_k", "attn_v"):
                if bspec is None:
                    # Batch too small to shard (long_500k, B=1): shard the
                    # sequence over the model axis.  Spreading S over
                    # (data x model) doesn't help: the per-step cache write
                    # (dynamic_update_slice at `length`) makes GSPMD reshard
                    # to this same model-only layout internally anyway
                    # (measured: identical footprint), so pin it explicitly.
                    return P(None, None, _maybe(mesh, s, model), None, None)
                return P(None, bspec, _maybe(mesh, s, model), None, None)
            # recurrent state [L, B, H, K, V]
            return P(None, bspec, _maybe(mesh, s, model), None, None)
        if nd == 4:   # [L, B, H, P*N...] / [L, B, K-1, C] conv
            L, b, a, c = shape
            return P(None, _maybe(mesh, b, dp), None,
                     _maybe(mesh, c, model))
        if nd == 3:   # [B, S, D] enc_out / [L, B, D] shifts
            a, b, c = shape
            if name == "enc_out":
                return P(_maybe(mesh, a, dp), None, None)
            return P(None, _maybe(mesh, b, dp), None)
        if nd == 2:
            return P(_maybe(mesh, shape[0], dp), None)
        return P()

    return jax.tree_util.tree_map_with_path(spec, cache_shape)


# -----------------------------------------------------------------------------
# fleet control plane (camera-axis data parallelism)
# -----------------------------------------------------------------------------


def fleet_mesh(devices=None) -> Mesh:
    """One-axis ``("cams",)`` mesh for the fleet control plane.

    ``devices`` is a ``Mesh`` (used as given -- must carry a ``cams`` axis),
    an int (first k host devices; on CPU CI, k > 1 needs
    ``XLA_FLAGS=--xla_force_host_platform_device_count=k`` set before jax
    import), an explicit device sequence, or None (all devices).
    """
    if isinstance(devices, Mesh):
        if "cams" not in devices.axis_names:
            raise ValueError("fleet mesh needs a 'cams' axis, got "
                             f"{devices.axis_names}")
        return devices
    if devices is None:
        devs = jax.devices()
    elif isinstance(devices, int):
        avail = jax.devices()
        if not 1 <= devices <= len(avail):
            raise ValueError(
                f"mesh wants {devices} devices but only {len(avail)} are "
                "available (set XLA_FLAGS=--xla_force_host_platform_"
                "device_count=N before importing jax)")
        devs = avail[:devices]
    else:
        devs = list(devices)
    return Mesh(np.asarray(devs), ("cams",))


def padded_lane_count(n: int, mesh: Mesh | None) -> int:
    """Smallest lane count >= n divisible by the mesh's device count."""
    if mesh is None:
        return n
    m = int(mesh.devices.size)
    return -(-n // m) * m


def fleet_sharding(mesh: Mesh) -> NamedSharding:
    """The lane-axis sharding of every fleet tick operand: dim 0 split over
    ``cams``, everything else replicated (prefix spec covers any rank).

    Pinning this as the jitted tick's in/out shardings keeps the compile
    cache at ONE variant: without it, the first dispatch sees host-committed
    arrays while later dispatches feed back the sharded outputs -- two
    distinct input layouts, two compiles.
    """
    return NamedSharding(mesh, P("cams"))


def shard_fleet_tick(fn, mesh: Mesh):
    """Partition a per-lane fleet tick over the ``cams`` axis.

    Every argument and output leaf carries the lane axis at dim 0 (the
    caller pads lanes to a device multiple with ``padded_lane_count``), so
    a prefix ``P("cams")`` spec covers the whole pytree of each.  Lanes are
    fully independent -- no collectives -- so sharding is pure data
    parallelism and cannot change numerics.
    """
    spec = P("cams")
    return jax.shard_map(fn, mesh=mesh, in_specs=(spec,) * 8,
                         out_specs=spec, check_vma=False)


def logical_to_sharding(specs: Any, mesh: Mesh):
    return jax.tree_util.tree_map(
        lambda s: NamedSharding(mesh, s),
        specs, is_leaf=lambda x: isinstance(x, P))


def shardings_for(tree_shape: Any, specs: Any, mesh: Mesh):
    return logical_to_sharding(specs, mesh)
