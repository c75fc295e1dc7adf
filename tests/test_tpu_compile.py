"""Ahead-of-time compiles of the device path for a described TPU v5e.

The TPU compiler is installed beside the CPU backend, so the main path's
device programs compile here for a chip that is described, not attached:
what Mosaic or XLA:TPU would refuse on the chip (block shapes, VMEM,
lowering rules, device memory) fails these tests without one.  Sizes are
the ``configs/mez_edge`` deployment's: 144x256 frames with the grid
engine's 48-frame bucket, the labeler at its two full-resolution group
shapes (144x256, and 216x256 for packed yuv420), and a 4096-lane fleet
tick.

The topology is described inside a fixture, never at import: only one
process may hold the TPU library, and every test worker imports this
file.  The persistent compilation cache is off around these compiles (an
entry written for a described chip cannot be read back without one).
"""

import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

H, W = 144, 256          # configs/mez_edge frame size
FRAMES = 48              # grid_engine bucket for 1 background + 32 frames
LANES = 4096


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure means "cannot"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _spec(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


@pytest.mark.parametrize("cs", [1, 2], ids=["gray", "yuv420"])
@pytest.mark.parametrize("knob4", [False, True], ids=["plain", "knob4"])
def test_frame_knob_grid_compiles(one_chip, cs, knob4):
    from repro.core import knobs as K
    from repro.kernels import frame_knobs as FK

    plan = FK.build_transform_plan(
        H, W, scale=K.RESOLUTION_SCALES[1], cs=cs, blur_ks=K.BLUR_KERNELS,
        art_modes=(0, 1, 2) if knob4 else (0,))

    def group(frames, prev, bg, enable):
        return FK.frame_knob_grid(frames, prev, plan,
                                  background=bg if knob4 else None,
                                  art_enable=enable if knob4 else None)

    clip = _spec((FRAMES, H, W, 3), jnp.uint8, one_chip)
    compiled = jax.jit(group).lower(
        clip, clip, _spec((H, W, 3), jnp.uint8, one_chip),
        _spec((FRAMES,), jnp.int32, one_chip)).compile()
    assert "tpu_custom_call" in compiled.as_text()
    payload, feats, changed = compiled.out_info
    assert payload.shape == (plan.n_settings, FRAMES, 1, plan.out_h,
                             plan.out_w)
    assert feats.shape == (plan.n_settings, FRAMES, FK.N_PROXY_FEATURES)
    assert changed.shape == (plan.n_settings, FRAMES)


@pytest.mark.parametrize("gh,gw", [(H, W), (216, W)],
                         ids=["144x256", "216x256"])
def test_label_group_compiles(one_chip, gh, gw):
    from repro.core import grid_engine as GE

    s, f = 15, 32
    compiled = GE._label_group.lower(
        _spec((s, f, gh, gw), jnp.float32, one_chip),
        _spec((s, f), jnp.float32, one_chip)).compile()
    assert compiled.out_info.shape == (s, f, gh, gw)
    # segmented scans, no gather: a gather runs about an element at a time
    assert " gather(" not in compiled.as_text()
    # the whole labeler fits the chip with room to spare
    assert compiled.memory_analysis().temp_size_in_bytes < 2 ** 30


def test_fused_fleet_tick_compiles(one_chip):
    from repro.core.broker import TABLE_CAPACITY
    from repro.core.characterization import CharacterizationTable
    from repro.core.controller import (ControllerParams, JaxControllerTables,
                                       fleet_controller_init,
                                       fused_fleet_tick, stack_params,
                                       stack_tables)
    from repro.core.drift import DriftConfig, DriftParams, drift_init
    from repro.core.knobs import KnobSetting

    sizes = np.linspace(2e3, 9e4, 24)
    accs = np.linspace(0.9, 1.0, 24)
    table = CharacterizationTable(
        settings=tuple(KnobSetting(resolution=i % 5) for i in range(24)),
        sizes_sorted=sizes, best_acc=accs, best_idx=np.arange(24),
        acc_by_setting=accs, size_by_setting=sizes)
    with jax.default_device(jax.devices("cpu")[0]):
        tables = stack_tables([JaxControllerTables.from_table(
            table, capacity=TABLE_CAPACITY)])
        params = stack_params([ControllerParams.from_scalars(
            latency_target=0.1, accuracy_target=0.95, slope=1.2e-6,
            intercept=0.008)])
        one_lane = (fleet_controller_init(tables), drift_init(1, 8),
                    np.zeros(1, np.float32), np.zeros(1, np.float32),
                    np.zeros(1, bool), tables, params,
                    DriftParams.from_config(DriftConfig(window=8), 1))
    # the same operands at LANES lanes, as shapes on the described chip
    args = jax.tree_util.tree_map(
        lambda x: _spec((LANES,) + np.shape(x)[1:], x.dtype, one_chip),
        one_lane)
    compiled = jax.jit(fused_fleet_tick).lower(*args).compile()
    new_ctrl, _, aux = compiled.out_info
    assert new_ctrl.current_idx.shape == (LANES,)
    assert aux.codes.shape == (LANES, 5)
