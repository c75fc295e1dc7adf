"""Production mesh construction.

``make_production_mesh`` is a FUNCTION (not a module-level constant) so
importing this module never touches jax device state; the dry-run sets
XLA_FLAGS before any jax import to get 512 host devices.

Production topology (TPU v5e): 16x16 = 256 chips per pod; multi-pod adds a
leading "pod" axis (2 pods = 512 chips).  The pod axis composes with "data"
for DP/FSDP; "model" is the intra-pod TP/SP axis (ICI-only collectives).
"""

from __future__ import annotations

import jax
from jax.sharding import AxisType

__all__ = ["make_production_mesh", "make_mesh", "HardwareSpec", "V5E"]

import dataclasses


@dataclasses.dataclass(frozen=True)
class HardwareSpec:
    """Roofline constants (per chip)."""
    name: str
    peak_flops_bf16: float     # FLOP/s
    hbm_bandwidth: float       # bytes/s
    ici_bandwidth: float       # bytes/s per link
    hbm_bytes: float


V5E = HardwareSpec(
    name="tpu-v5e",
    peak_flops_bf16=197e12,
    hbm_bandwidth=819e9,
    ici_bandwidth=50e9,
    hbm_bytes=16e9,
)


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_mesh(shape: tuple[int, ...], axes: tuple[str, ...]):
    """Arbitrary mesh for tests/benchmarks (e.g. (1, 1) on one CPU device).

    Axes are ``Auto``: the train step places values with
    ``with_sharding_constraint``, which ``jax.make_mesh``'s default
    ``Explicit`` axes reject."""
    return jax.make_mesh(shape, axes,
                         axis_types=(AxisType.Auto,) * len(axes))
