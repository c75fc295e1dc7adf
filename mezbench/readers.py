"""Arithmetic the metric readers share.  Each metric has its own reader,
``metrics/<name>.py``, with one function ``read(run)`` that returns the
number or None when the run has nothing to read for it."""

from __future__ import annotations

import numpy as np

from . import harness, kernel_cost
from .reference import knobs as RK

GRID_MODULE = "_grid_call"          # jit of frame_knobs._grid_call


def p95_ms(values_s) -> float | None:
    if not values_s:
        return None
    return float(np.percentile(np.asarray(values_s, np.float64), 95) * 1e3)


def mean_poll_ms(run) -> float | None:
    polls = getattr(run, "poll_s", None)
    if not polls:
        return None
    return float(np.sum(polls) / len(polls) * 1e3)


def transforms_per_frame(run) -> float | None:
    if not getattr(run, "delivered", 0):
        return None
    return run.cache_misses / run.delivered


def idle_share(run) -> float | None:
    s = run.summary
    if s is None or not s.devices or s.window_s <= 0:
        return None
    return 1.0 - s.busy_s / s.window_s


def device_seconds(run, module: str) -> float | None:
    """Device seconds of the XLA modules whose name contains ``module``."""
    if run.summary is None:
        return None
    t, _ = run.summary.module_seconds(module)
    return t if t > 0 else None


def per_sweep_ms(run, name: str) -> float | None:
    t = device_seconds(run, name)
    sweeps = getattr(run, "sweeps_s", None)
    if t is None or not sweeps:
        return None
    return t / len(sweeps) * 1e3


def grid_roofline(run) -> float | None:
    """Least time of a sweep's grid calls (``kernel_cost``) over their
    device time per sweep, in percent."""
    t = per_sweep_ms(run, GRID_MODULE)
    if t is None:
        return None
    import jax

    peak = harness.load_peaks()[jax.devices()[0].device_kind]
    cfg = run.config
    least, _ = kernel_cost.sweep_min_seconds(
        run.plans, cfg["frame_height"], cfg["frame_width"],
        run.clip_len + 1, len(RK.BLUR_KERNELS),
        3 if cfg["include_artifact"] else 1, peak)
    return least / (t / 1e3) * 100.0


def per_dispatch_us(run, module: str) -> float | None:
    if run.summary is None:
        return None
    t, n = run.summary.module_seconds(module)
    return t / n * 1e6 if n else None
