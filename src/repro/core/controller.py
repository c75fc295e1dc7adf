"""The Mez network latency controller (paper Section 4.2, Algorithm 1).

Two implementations with identical control law:

``LatencyController``  -- host-side, lives next to the CamBroker (the paper's
                          deployment: a microservice on the IoT camera node).
``controller_step``    -- pure-JAX, jittable (lax-only control flow).  This is
                          the paper's future-work item "integrating the
                          controller as a part of the CamBroker" taken to its
                          TPU-native conclusion: the controller can run inside
                          a compiled step, where it drives the approximate-
                          collective knob (core/approx_comm.py).

``JaxControllerTables`` are TRACED inputs of ``controller_step``: padded to a
fixed ``capacity`` with an ``n_valid`` row count, a freshly characterized
table (``grid_engine.refresh_tables``) hot-swaps into a compiled step with no
recompile -- ``swap_tables`` reuses the live tables' donated device buffers.
That closes the online re-characterization loop: ``Session.update_qos``
re-runs the batched sweep and the very next compiled step consumes the new
tables.

Control law (Algorithm 1):

    nominal   = Regression^-1(latency_target)              # bytes
    error     = latency_sampled - latency_target           # seconds
    size      = nominal + K1 * error + K2 * integral(error)
    accuracy, knob = Table.query(size)                     # BST + hash lookups
    if accuracy >= accuracy_target: apply knob
    else: report infeasible (application decides: relax or fail)

K1, K2 < 0: positive latency error shrinks the requested size.  Gains are
auto-scaled from the regression slope so they are expressed in natural units
("how many bytes does one second of error buy").
"""

from __future__ import annotations

import dataclasses
import functools
from collections import deque
from collections.abc import Mapping

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation

from repro.core.characterization import CharacterizationTable, LatencyRegression
from repro.core.drift import (DriftConfig, DriftParams, DriftState,
                              _drift_lane_step, drift_init)
from repro.core.knobs import KnobSetting

__all__ = ["ControllerConfig", "ControlDecision", "LatencyController",
           "JaxControllerTables", "ControllerState", "controller_init",
           "controller_step", "swap_tables", "ControllerParams", "StepAux",
           "stack_tables", "stack_params", "fleet_controller_init",
           "fleet_controller_step", "fleet_swap_tables", "FusedTickAux",
           "fused_fleet_tick", "FleetTickResult", "FleetController"]


@dataclasses.dataclass(frozen=True)
class ControllerConfig:
    latency_target: float            # seconds (paper: 100 ms)
    accuracy_target: float           # normalized F1 floor (paper: 0.95-0.96)
    error_threshold: float = 0.010   # seconds; inside the band = no action
    alpha_p: float = 0.8             # K1 = -alpha_p / slope
    alpha_i: float = 0.25            # K2 = -alpha_i / slope
    integral_clip: float = 1.0       # anti-windup, seconds*samples
    relax: bool = True               # also act when latency is far BELOW target
                                     # (paper's Alg. 1 is one-sided; relaxation
                                     # restores quality after interference ends)


@dataclasses.dataclass(frozen=True)
class ControlDecision:
    feasible: bool
    setting: KnobSetting | None
    setting_index: int
    predicted_accuracy: float
    requested_size: float
    error: float
    acted: bool


class LatencyController:
    """Host-side PI controller (one per IoT camera node; no central control,
    so camera nodes scale independently -- paper Section 4.2)."""

    def __init__(self, config: ControllerConfig, table: CharacterizationTable,
                 regression: LatencyRegression):
        self.config = config
        self.table = table
        self.regression = regression
        self.integral = 0.0
        self.k1 = -config.alpha_p / max(regression.slope, 1e-12)
        self.k2 = -config.alpha_i / max(regression.slope, 1e-12)
        self._nominal = regression.invert(config.latency_target)
        # Algorithm 1: the starting operating point is the nominal size the
        # regression model predicts for the latency target (not full quality).
        _, idx = self.table.query_size(
            float(np.clip(self._nominal, self.table.sizes_sorted[0],
                          self.table.sizes_sorted[-1])))
        self._current = int(idx)

    def set_target(self, latency_target: float, accuracy_target: float) -> None:
        """The CamBroker's internal SetTarget API (paper Fig. 9).

        Runtime retarget: callable mid-stream (v2 ``update_qos``).  Besides
        resetting the integral, the operating point is re-seeded from the new
        target's nominal size so the renegotiated bounds take effect on the
        very next fetch -- within one control interval -- instead of waiting
        for the error signal to walk the old setting over.
        """
        self.config = dataclasses.replace(
            self.config, latency_target=latency_target,
            accuracy_target=accuracy_target)
        self._nominal = self.regression.invert(latency_target)
        self.integral = 0.0
        _, idx = self.table.query_size(
            float(np.clip(self._nominal, self.table.sizes_sorted[0],
                          self.table.sizes_sorted[-1])))
        self._current = int(idx)

    def swap_table(self, table: CharacterizationTable) -> None:
        """Hot-swap a freshly characterized table (online
        re-characterization).  Unlike ``set_target`` this keeps the PI
        state: the integral carries over (network conditions did not reset
        just because the tables did) and only the operating point is
        re-seeded into the new table's size axis."""
        self.table = table
        _, idx = table.query_size(
            float(np.clip(self._nominal, table.sizes_sorted[0],
                          table.sizes_sorted[-1])))
        self._current = int(idx)

    def update(self, latency_sampled: float,
               budget_scale: float = 1.0) -> ControlDecision:
        """One PI step.  ``budget_scale`` caps the nominal operating size
        (fleet admission control's per-tenant degradation knob; 1.0 -- the
        single-tenant case -- is exact, so decisions are unchanged)."""
        cfg = self.config
        nominal = self._nominal * budget_scale
        error = latency_sampled - cfg.latency_target
        act = error > cfg.error_threshold or (
            cfg.relax and error < -cfg.error_threshold)
        if not act:
            # inside the band: hold the current setting
            idx = self._current
            acc = float(self.table.acc_by_setting[idx]) if idx >= 0 else 0.0
            return ControlDecision(idx >= 0, self.table.setting_for(idx) if idx >= 0
                                   else None, idx, acc, nominal, error, False)
        self.integral = float(np.clip(self.integral + error,
                                      -cfg.integral_clip, cfg.integral_clip))
        size = nominal + self.k1 * error + self.k2 * self.integral
        size = float(np.clip(size, self.table.sizes_sorted[0],
                             self.table.sizes_sorted[-1]))
        accuracy, idx = self.table.query_size(size)
        if accuracy >= cfg.accuracy_target and idx >= 0:
            self._current = idx
            return ControlDecision(True, self.table.setting_for(idx), idx,
                                   accuracy, size, error, True)
        # Paper: "If the application requested latency and accuracy are
        # infeasible, the application is notified.  At this point, the
        # application has to decide whether to continue operation with
        # relaxed latency/accuracy requirements, or notify the system
        # operator of failure."  We notify (feasible=False) AND return the
        # best-accuracy setting within the size budget so a subscriber that
        # chooses "continue relaxed" degrades gracefully instead of
        # reverting to raw frames.
        if idx >= 0:
            self._current = idx
        return ControlDecision(False,
                               self.table.setting_for(idx) if idx >= 0 else None,
                               idx, accuracy, size, error, True)

    @property
    def current_setting(self) -> KnobSetting | None:
        return self.table.setting_for(self._current) if self._current >= 0 else None


# =============================================================================
# Jittable controller
# =============================================================================


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class JaxControllerTables:
    """Characterization tables as device arrays (sorted by size).

    Every field is a pytree LEAF, so the whole object is a traced input of
    ``controller_step`` -- refreshed values flow into a compiled step
    without retracing.  ``from_table(capacity=)`` pads the row axis to a
    fixed size (``sizes_sorted`` with +inf so ``searchsorted`` never lands
    in the padding) and records the live row count in ``n_valid``; tables
    of any kept-set size then share ONE compiled step, which is what makes
    online re-characterization swap-in free.
    """
    sizes_sorted: jax.Array   # f32[capacity], +inf beyond n_valid
    best_acc: jax.Array       # f32[capacity]
    best_idx: jax.Array       # i32[capacity], -1 beyond n_valid
    n_valid: jax.Array = None  # i32[], live rows (defaults to capacity)
    codes: jax.Array = None   # i32[capacity, 5] knob codes per SETTING index
    #                           (resolution, colorspace, blur, artifact,
    #                           diff) -- what the fused fleet tick gathers so
    #                           the host rebuilds a KnobSetting without
    #                           touching the Python table on the poll path

    def __post_init__(self):
        if self.n_valid is None:
            self.n_valid = jnp.asarray(self.sizes_sorted.shape[0], jnp.int32)
        if self.codes is None:
            self.codes = jnp.zeros((self.sizes_sorted.shape[0], 5), jnp.int32)

    def tree_flatten(self):
        return ((self.sizes_sorted, self.best_acc, self.best_idx,
                 self.n_valid, self.codes), None)

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children)

    @classmethod
    def from_table(cls, table: CharacterizationTable, *,
                   capacity: int | None = None) -> "JaxControllerTables":
        a = table.as_arrays()
        n = a["sizes_sorted"].shape[0]
        cap = n if capacity is None else int(capacity)
        if cap < n:
            raise ValueError(f"capacity {cap} < {n} characterized settings")
        pad = cap - n
        sizes = np.concatenate([a["sizes_sorted"],
                                np.full(pad, np.inf, np.float32)])
        acc = np.concatenate([a["best_acc"], np.zeros(pad, np.float32)])
        idx = np.concatenate([a["best_idx"], np.full(pad, -1, np.int32)])
        codes = np.zeros((cap, 5), np.int32)
        codes[:len(table.settings)] = [
            (s.resolution, s.colorspace, s.blur, s.artifact, s.diff)
            for s in table.settings]
        return cls(jnp.asarray(sizes), jnp.asarray(acc), jnp.asarray(idx),
                   jnp.asarray(n, jnp.int32), jnp.asarray(codes))


def swap_tables(live: JaxControllerTables | None,
                fresh: JaxControllerTables) -> JaxControllerTables:
    """Hot-swap refreshed tables into a running compiled consumer.

    With matching capacities the swap is shape-stable (no recompile of any
    jitted step consuming the tables); on accelerator backends the live
    tables' buffers are donated so XLA reuses them in place instead of
    allocating.  Shape mismatch (capacity changed) falls through to the
    fresh tables -- consumers recompile once, which is the correct cost.
    """
    if live is None:
        return fresh
    live_leaves = jax.tree_util.tree_leaves(live)
    fresh_leaves = jax.tree_util.tree_leaves(fresh)
    if any(l.shape != f.shape or l.dtype != f.dtype
           for l, f in zip(live_leaves, fresh_leaves)):
        return fresh
    if jax.default_backend() == "cpu":
        # donation is a no-op on CPU; skip the jit round-trip (and its
        # "donated buffers were not usable" warning)
        return fresh
    return _swap_tables_donated(live, fresh)


@functools.partial(jax.jit, donate_argnums=(0,))
def _swap_tables_donated(live: JaxControllerTables,
                         fresh: JaxControllerTables) -> JaxControllerTables:
    del live  # buffers reused by XLA for the identically-shaped output
    return fresh


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class ControllerState:
    integral: jax.Array       # f32[]
    current_idx: jax.Array    # i32[]
    feasible: jax.Array       # bool[]
    last_error: jax.Array     # f32[]

    def tree_flatten(self):
        return ((self.integral, self.current_idx, self.feasible,
                 self.last_error), None)

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children)


def controller_init(tables: JaxControllerTables, *,
                    start_idx: int | jax.Array | None = None
                    ) -> ControllerState:
    """Initial state: the highest-fidelity characterized setting, or an
    explicit ``start_idx`` (e.g. the host controller's seeded operating
    point, for lockstep host/jit comparisons)."""
    if start_idx is None:
        start = jnp.take(tables.best_idx, tables.n_valid - 1)
    else:
        start = jnp.asarray(start_idx)
    return ControllerState(
        integral=jnp.zeros((), jnp.float32),
        current_idx=start.astype(jnp.int32),
        feasible=jnp.ones((), bool),
        last_error=jnp.zeros((), jnp.float32),
    )


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class ControllerParams:
    """The control-law constants of Algorithm 1 as TRACED leaves.

    For one camera every leaf is a scalar; ``stack_params`` stacks N of
    them into ``f32[N]`` lanes for the vmapped fleet step.  The gains are
    precomputed host-side in float64 (``k1``/``k2``/``nominal``) exactly as
    ``LatencyController`` does, so a compiled step fed these params is
    numerically identical to the scalar-kwarg ``controller_step`` -- and a
    per-camera retarget (new targets, same shapes) flows into a compiled
    consumer without retracing.
    """
    latency_target: jax.Array    # f32
    accuracy_target: jax.Array   # f32
    error_threshold: jax.Array   # f32
    k1: jax.Array                # f32, -alpha_p / slope (bytes per second)
    k2: jax.Array                # f32, -alpha_i / slope
    nominal: jax.Array           # f32, Regression^-1(latency_target), bytes
    integral_clip: jax.Array     # f32
    relax: jax.Array             # bool
    # multi-tenant axes: admission control reallocates the shared wire
    # budget by writing these leaves (values, not shapes -- no retrace).
    budget_scale: jax.Array = None  # f32, cap on nominal (1.0 = full budget)
    tier: jax.Array = None          # i32, tenant SLO preemption priority

    def __post_init__(self):
        if self.budget_scale is None:
            self.budget_scale = jnp.float32(1.0)
        if self.tier is None:
            self.tier = jnp.int32(0)

    def tree_flatten(self):
        return ((self.latency_target, self.accuracy_target,
                 self.error_threshold, self.k1, self.k2, self.nominal,
                 self.integral_clip, self.relax, self.budget_scale,
                 self.tier), None)

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children)

    @classmethod
    def from_scalars(cls, *, latency_target: float, accuracy_target: float,
                     slope: float, intercept: float,
                     error_threshold: float = 0.010, alpha_p: float = 0.8,
                     alpha_i: float = 0.25, integral_clip: float = 1.0,
                     relax: bool = True, budget_scale: float = 1.0,
                     tier: int = 0) -> "ControllerParams":
        k1 = -alpha_p / max(slope, 1e-12)
        k2 = -alpha_i / max(slope, 1e-12)
        nominal = max(0.0, (latency_target - intercept) / max(slope, 1e-12))
        return cls(jnp.float32(latency_target), jnp.float32(accuracy_target),
                   jnp.float32(error_threshold), jnp.float32(k1),
                   jnp.float32(k2), jnp.float32(nominal),
                   jnp.float32(integral_clip), jnp.asarray(relax),
                   jnp.float32(budget_scale), jnp.int32(tier))

    @classmethod
    def from_controller(cls, host: "LatencyController", *,
                        budget_scale: float = 1.0,
                        tier: int = 0) -> "ControllerParams":
        """Mirror a live host controller's law (gains/nominal copied verbatim
        from the float64 host state, so fleet decisions track host decisions).
        ``budget_scale``/``tier`` carry the owning subscription's admission
        cap and SLO class -- per-subscription state the host controller
        (shared across tenants) does not own."""
        cfg = host.config
        return cls(jnp.float32(cfg.latency_target),
                   jnp.float32(cfg.accuracy_target),
                   jnp.float32(cfg.error_threshold), jnp.float32(host.k1),
                   jnp.float32(host.k2), jnp.float32(host._nominal),
                   jnp.float32(cfg.integral_clip), jnp.asarray(cfg.relax),
                   jnp.float32(budget_scale), jnp.int32(tier))


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class StepAux:
    """Per-step decision detail (everything ``CamBroker.fetch`` needs to act
    on a decision without re-running the host control law)."""
    idx: jax.Array             # i32, chosen setting (-1 = none / raw frames)
    feasible: jax.Array        # bool, accuracy floor met at the size budget
    acted: jax.Array           # bool, outside the error band this step
    error: jax.Array           # f32, latency error (seconds)
    requested_size: jax.Array  # f32, PI output (bytes), nominal when holding
    accuracy: jax.Array        # f32, best accuracy at the size budget

    def tree_flatten(self):
        return ((self.idx, self.feasible, self.acted, self.error,
                 self.requested_size, self.accuracy), None)

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children)


def _controller_step_core(state: ControllerState, latency_sampled: jax.Array,
                          tables: JaxControllerTables,
                          params: ControllerParams, *,
                          best_effort: bool = False
                          ) -> tuple[ControllerState, StepAux]:
    """One PI update with traced params -- the shared scalar/fleet core.

    ``best_effort`` selects the infeasible-step semantics: False keeps the
    raw jittable contract (knob index -> -1, consumer falls back to raw
    frames); True mirrors the host ``LatencyController`` (serve the
    best-accuracy setting within budget, notify via the feasible flag) --
    what the fleet-backed broker path uses.
    """
    lat = jnp.asarray(latency_sampled, jnp.float32)
    error = lat - params.latency_target
    act = (error > params.error_threshold) | (
        params.relax & (error < -params.error_threshold))

    new_integral = jnp.clip(state.integral + error,
                            -params.integral_clip, params.integral_clip)
    integral = jnp.where(act, new_integral, state.integral)

    nominal = params.nominal * params.budget_scale
    size = nominal + params.k1 * error + params.k2 * integral
    # clip into the LIVE size range (padding rows carry +inf)
    hi = jnp.take(tables.sizes_sorted, tables.n_valid - 1)
    size = jnp.clip(size, tables.sizes_sorted[0], hi)
    pos = jnp.searchsorted(tables.sizes_sorted, size, side="right") - 1
    pos = jnp.clip(pos, 0, tables.n_valid - 1)
    accuracy = tables.best_acc[pos]
    idx = tables.best_idx[pos]

    ok = accuracy >= params.accuracy_target
    if best_effort:
        # host semantics: _current moves to the best-effort setting even on
        # an infeasible step (idx >= 0 guard matches the host's)
        chosen = jnp.where(idx >= 0, idx, state.current_idx)
    else:
        chosen = jnp.where(ok, idx, -1)
    new_idx = jnp.where(act, chosen, state.current_idx)
    new_feasible = jnp.where(act, ok, state.feasible)
    new_state = ControllerState(
        integral=integral,
        current_idx=new_idx.astype(jnp.int32),
        feasible=new_feasible,
        last_error=error,
    )
    # decision-shaped feasibility mirrors the host: an acted step reports
    # whether the floor was met, a hold reports whether a live setting is
    # being served (the STATE keeps the sticky flag for jit consumers)
    aux = StepAux(idx=new_state.current_idx,
                  feasible=jnp.where(act, ok, new_state.current_idx >= 0),
                  acted=act, error=error,
                  requested_size=jnp.where(act, size, nominal),
                  accuracy=accuracy)
    return new_state, aux


# mezlint: jit-entry
def controller_step(state: ControllerState, latency_sampled: jax.Array,
                    tables: JaxControllerTables, *,
                    latency_target: float, accuracy_target: float,
                    slope: float, intercept: float,
                    error_threshold: float = 0.010, alpha_p: float = 0.8,
                    alpha_i: float = 0.25, integral_clip: float = 1.0,
                    relax: bool = True) -> tuple[ControllerState, jax.Array]:
    """One PI update, fully traceable.  Returns (new_state, knob_index).

    ``tables`` is a TRACED input: hot-swapped tables (same capacity, any
    ``n_valid``) flow through a compiled caller with no retrace -- see
    ``swap_tables`` / ``JaxControllerTables.from_table(capacity=)``.

    knob_index is an i32 scalar indexing the characterized settings; -1 when
    no feasible setting exists (the compiled consumer falls back to the
    highest-fidelity payload and flags infeasibility, matching the paper's
    "notify the application" semantics).
    """
    params = ControllerParams.from_scalars(
        latency_target=latency_target, accuracy_target=accuracy_target,
        slope=slope, intercept=intercept, error_threshold=error_threshold,
        alpha_p=alpha_p, alpha_i=alpha_i, integral_clip=integral_clip,
        relax=relax)
    new_state, _ = _controller_step_core(state, latency_sampled, tables,
                                         params)
    return new_state, new_state.current_idx


# =============================================================================
# Fleet control plane: all cameras of a session in ONE compiled step
# =============================================================================


def stack_tables(tables: "Sequence[JaxControllerTables]"
                 ) -> JaxControllerTables:
    """Stack per-camera tables along a leading fleet axis.

    Every table must share one capacity (``JaxControllerTables.from_table``
    with a common ``capacity=``); per-camera ``n_valid`` row counts may
    differ freely -- that is what makes a per-camera hot-swap free.
    """
    caps = {t.sizes_sorted.shape[-1] for t in tables}
    if len(caps) != 1:
        raise ValueError(f"stack_tables needs one shared capacity, got {caps}")
    return jax.tree_util.tree_map(lambda *leaves: jnp.stack(leaves), *tables)


def stack_params(params: "Sequence[ControllerParams]") -> ControllerParams:
    """Stack per-camera control-law params along a leading fleet axis."""
    return jax.tree_util.tree_map(lambda *leaves: jnp.stack(leaves), *params)


def fleet_controller_init(tables: JaxControllerTables, *,
                          start_idx=None) -> ControllerState:
    """Stacked initial state for a fleet of N cameras (tables stacked along
    the leading axis).  ``start_idx`` seeds per-camera operating points
    (i32[N]); default is each camera's highest-fidelity setting."""
    n = tables.sizes_sorted.shape[0]
    if start_idx is None:
        start = jax.vmap(lambda t: jnp.take(t.best_idx, t.n_valid - 1))(tables)
    else:
        start = jnp.asarray(start_idx)
    return ControllerState(
        integral=jnp.zeros((n,), jnp.float32),
        current_idx=start.astype(jnp.int32),
        feasible=jnp.ones((n,), bool),
        last_error=jnp.zeros((n,), jnp.float32),
    )


def fleet_controller_step(states: ControllerState, latencies: jax.Array,
                          tables: JaxControllerTables,
                          params: ControllerParams
                          ) -> tuple[ControllerState, StepAux]:
    """One PI update for a WHOLE fleet: ``controller_step`` vmapped over the
    leading camera axis of every input, so N cameras cost one compiled
    dispatch instead of N (per-step Python overhead is ~flat in N).

    Uses host (best-effort) infeasible semantics -- this is the step the
    fleet-backed ``EdgeBroker.poll_subscription`` drives, and the broker's
    contract is the paper's "notify the application AND keep serving the
    best-accuracy setting within budget".
    """
    lats = jnp.asarray(latencies, jnp.float32)
    return jax.vmap(
        functools.partial(_controller_step_core, best_effort=True)
    )(states, lats, tables, params)


def fleet_swap_tables(live: JaxControllerTables, index,
                      fresh: JaxControllerTables) -> JaxControllerTables:
    """Hot-swap a SUBSET of per-camera tables inside a stacked fleet.

    ``index`` is an int (one camera) or int sequence; ``fresh`` is one
    table (capacity matching the stack) or a stack of ``len(index)`` tables.
    Shapes are unchanged, so every compiled consumer of the stack keeps its
    cache -- re-characterizing camera 17 of 256 never recompiles the fleet
    step.  Capacity mismatch is an error (grow the stack deliberately via
    ``FleetController`` instead)."""
    idx = jnp.atleast_1d(jnp.asarray(index, jnp.int32))
    cap_live = live.sizes_sorted.shape[-1]
    cap_fresh = fresh.sizes_sorted.shape[-1]
    if cap_live != cap_fresh:
        raise ValueError(f"fleet_swap_tables: capacity mismatch "
                         f"(stack {cap_live}, fresh {cap_fresh})")

    def put(leaf_live, leaf_fresh):
        leaf_fresh = jnp.asarray(leaf_fresh)
        if leaf_fresh.ndim == leaf_live.ndim - 1:      # single row
            leaf_fresh = leaf_fresh[None]
        return leaf_live.at[idx].set(leaf_fresh)

    return jax.tree_util.tree_map(put, live, fresh)


def _set_lane(tree, i: int, row):
    """Write one fleet lane of a stacked pytree (state/params row update)."""
    return jax.tree_util.tree_map(
        lambda stacked, v: stacked.at[i].set(v), tree, row)


# =============================================================================
# Fused fleet tick: drift + control + decision application, ONE dispatch
# =============================================================================


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class FusedTickAux:
    """Everything the host needs from one fused tick, in one transfer:
    the per-lane controller decision detail, the chosen setting's knob
    codes (so ``KnobSetting`` is rebuilt without touching the Python
    table), and the drift fire-set."""
    step: StepAux              # per-lane controller decision detail
    codes: jax.Array           # i32[..., 5], chosen setting's knob codes
    #                            (-1 rows when no live setting is served)
    fired: jax.Array           # bool[...], drift lane fired this tick
    score: jax.Array           # f32[...], drift windowed score

    def tree_flatten(self):
        return ((self.step, self.codes, self.fired, self.score), None)

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children)


def _fused_lane_core(ctrl_state: ControllerState, drift_state: DriftState,
                     latency: jax.Array, drift_err: jax.Array,
                     drift_valid: jax.Array, tables: JaxControllerTables,
                     params: ControllerParams, drift_params: DriftParams
                     ) -> tuple[ControllerState, DriftState, FusedTickAux]:
    """One camera's whole per-poll control plane, fused.

    Built on the SAME cores as the unfused path (``_drift_lane_step`` then
    ``_controller_step_core(best_effort=True)``), so fused decisions are
    bit-identical to the three-dispatch path -- the parity tests hold this
    lane by lane.  The drift observation is the residual the host
    aggregated at the END of the previous poll; a fire is reported in the
    aux for the host to act on (recharacterize + table swap + re-tick).
    """
    new_drift, fired, score = _drift_lane_step(drift_state, drift_err,
                                               drift_valid, drift_params)
    new_ctrl, aux = _controller_step_core(ctrl_state, latency, tables,
                                          params, best_effort=True)
    # decision application on device: gather the chosen setting's knob codes
    safe = jnp.clip(aux.idx, 0, tables.codes.shape[0] - 1)
    codes = jnp.where(aux.idx >= 0, jnp.take(tables.codes, safe, axis=0),
                      jnp.full((5,), -1, jnp.int32))
    return new_ctrl, new_drift, FusedTickAux(step=aux, codes=codes,
                                             fired=fired, score=score)


def fused_fleet_tick(ctrl_states: ControllerState, drift_states: DriftState,
                     latencies: jax.Array, drift_errs: jax.Array,
                     drift_valid: jax.Array, tables: JaxControllerTables,
                     params: ControllerParams, drift_params: DriftParams
                     ) -> tuple[ControllerState, DriftState, FusedTickAux]:
    """The whole fleet's per-poll control plane as ONE compiled dispatch:
    drift tick + PI step + decision->knob-code application, vmapped over
    the leading camera axis.  This is the function ``FleetController``
    jits (and, with a mesh, ``shard_map``s over the camera axis -- every
    lane is independent, so lane sharding cannot change numerics)."""
    lats = jnp.asarray(latencies, jnp.float32)
    errs = jnp.asarray(drift_errs, jnp.float32)
    valid = jnp.asarray(drift_valid, bool)
    return jax.vmap(_fused_lane_core)(ctrl_states, drift_states, lats, errs,
                                      valid, tables, params, drift_params)


class FleetTickResult(Mapping):
    """Lazy ``camera_id -> ControlDecision`` view over one fused tick.

    ``poll_subscription`` only materializes decisions for the cameras it
    actually fetches this poll (O(fetched), not O(N)); iterating the
    mapping (the dict-compat ``FleetController.decide`` path) materializes
    every lane.  ``setting`` is rebuilt from the tick's gathered knob codes
    -- ``KnobSetting`` is a frozen value type, so this equals the host
    table's ``setting_for(idx)`` bit for bit.
    """

    __slots__ = ("fired_cams", "_cam_ids", "_lane", "_aux", "_cache")

    def __init__(self, cam_ids, lane_map, aux_host, fired_cams):
        self._cam_ids = cam_ids
        self._lane = lane_map
        self._aux = aux_host            # device_get'd FusedTickAux (padded)
        self._cache: dict[int, ControlDecision] = {}
        self.fired_cams = fired_cams    # drift fire-set, lane order

    def _materialize(self, i: int) -> ControlDecision:
        d = self._cache.get(i)
        if d is None:
            a = self._aux
            idx = int(a.step.idx[i])
            setting = (KnobSetting(*(int(c) for c in a.codes[i]))
                       if idx >= 0 else None)
            d = ControlDecision(
                feasible=bool(a.step.feasible[i]), setting=setting,
                setting_index=idx,
                predicted_accuracy=float(a.step.accuracy[i]),
                requested_size=float(a.step.requested_size[i]),
                error=float(a.step.error[i]), acted=bool(a.step.acted[i]))
            self._cache[i] = d
        return d

    def get(self, cid, default=None):
        i = self._lane.get(cid)
        return default if i is None else self._materialize(i)

    def __getitem__(self, cid) -> ControlDecision:
        i = self._lane.get(cid)
        if i is None:
            raise KeyError(cid)
        return self._materialize(i)

    def __iter__(self):
        return iter(self._cam_ids)

    def __len__(self) -> int:
        return len(self._cam_ids)


class FleetController:
    """Host-side orchestrator: N per-camera control planes as ONE jitted
    ``fused_fleet_tick`` (PI step + drift tick + decision application).

    Built over live ``CamBroker``-like objects (anything carrying
    ``camera_id``, ``controller``, ``table_version``, ``qos_version``); the
    brokers' host controllers stay the source of truth for tables, targets
    and law constants, while the PI *state* (integral, operating point)
    lives here on device.  ``sync()`` diffs the brokers' version counters
    and hot-swaps changed lanes (tables via ``fleet_swap_tables``, targets
    via a params-row write) without recompiling; only a table that outgrows
    the shared capacity rebuilds the stack, which recompiles once -- the
    correct cost.

    ``mesh`` partitions the tick over the camera axis with ``shard_map``
    (``repro.sharding.partition.fleet_mesh``): an int selects that many
    host devices, a ``jax.sharding.Mesh`` is used as given, ``None`` stays
    single-device.  Lanes are padded up to a device multiple (padding lanes
    replicate lane 0 and are fed hold inputs; their outputs are never
    read), and every lane is independent, so sharding never changes
    numerics -- the 8-device parity test holds fused==host bit for bit.
    """

    HISTORY_LIMIT = 4096

    def __init__(self, cams, *, capacity: int | None = None,
                 record_history: bool = False, mesh=None, tier: int = 0):
        cams = list(cams)
        # multi-tenant axes: the owning subscription's SLO class rides as a
        # per-lane i32 leaf, and admission control caps the fleet's wire
        # budget by writing the per-lane budget_scale leaf (set_budget_scale)
        self._tier = int(tier)
        self._budget_scale = 1.0
        if not cams:
            raise ValueError("FleetController needs at least one camera")
        for cam in cams:
            if cam.controller is None:
                raise ValueError(
                    f"camera {cam.camera_id!r} has no controller installed")
        self._cams = cams
        self.cam_ids = [c.camera_id for c in cams]
        self.lane_of = {cid: i for i, cid in enumerate(self.cam_ids)}
        need = max(len(c.controller.table.settings) for c in cams)
        self.capacity = max(need, capacity or 0)
        self.record_history = record_history
        self.history: "deque" = deque(maxlen=self.HISTORY_LIMIT)
        self.mesh = None
        tick_fn = fused_fleet_tick
        if mesh is not None:
            from repro.sharding import partition
            self.mesh = partition.fleet_mesh(mesh)
            tick_fn = partition.shard_fleet_tick(fused_fleet_tick, self.mesh)
        n = len(cams)
        lanes_mult = self.mesh.devices.size if self.mesh is not None else 1
        self.n_lanes = n
        self._n_padded = -(-n // lanes_mult) * lanes_mult
        # wrap in a per-instance function object: jax.jit keys its tracing
        # cache on the callable, so each fleet gets its own cache and
        # ``cache_size()`` counts THIS fleet's compiled variants only.  On a
        # mesh the lane sharding is pinned AND every dispatch normalizes its
        # operands onto it (``device_put`` below): poll T feeds back poll
        # T-1's sharded outputs while poll 0 sees host arrays, and without
        # the normalization that placement split registers as a second
        # cache entry even though the traced program is identical.
        self._sharding = None
        jit_kwargs = {}
        if self.mesh is not None:
            self._sharding = partition.fleet_sharding(self.mesh)
            jit_kwargs = dict(in_shardings=self._sharding,
                              out_shardings=self._sharding)
        self._tick_jit = jax.jit(
            lambda cs, ds, lat, de, dv, tb, pr, dp: tick_fn(
                cs, ds, lat, de, dv, tb, pr, dp), **jit_kwargs)
        # drift lanes: a bound DriftMonitor's state rides in the fused tick;
        # without one, a window-1 placeholder holds forever (valid=False,
        # count pinned at 0 < min_samples, so it can never fire)
        self._drift = None
        self._drift_window = 1
        self._drift_state = drift_init(self._n_padded, 1)
        self._drift_params = DriftParams.from_config(
            DriftConfig(window=1), self._n_padded)
        self._pre_state = None
        self._build_stack()

    # -- stack assembly ------------------------------------------------------
    def _pad_rows(self, values, pad):
        return list(values) + [values[0]] * pad

    def _build_stack(self) -> None:
        pad = self._n_padded - self.n_lanes
        rows = [JaxControllerTables.from_table(c.controller.table,
                                               capacity=self.capacity)
                for c in self._cams]
        self.tables = stack_tables(self._pad_rows(rows, pad))
        self.params = stack_params(self._pad_rows(
            [ControllerParams.from_controller(c.controller,
                                              budget_scale=self._budget_scale,
                                              tier=self._tier)
             for c in self._cams], pad))
        start = np.asarray(self._pad_rows(
            [c.controller._current for c in self._cams], pad), np.int32)
        state = fleet_controller_init(self.tables, start_idx=start)
        self.state = ControllerState(
            integral=jnp.asarray(self._pad_rows(
                [c.controller.integral for c in self._cams], pad),
                jnp.float32),
            current_idx=state.current_idx,
            feasible=state.feasible,
            last_error=state.last_error)
        self._table_versions = [c.table_version for c in self._cams]
        self._qos_versions = [c.qos_version for c in self._cams]
        self._targets = np.asarray(self._pad_rows(
            [c.controller.config.latency_target for c in self._cams], pad),
            np.float32)

    def attach_drift(self, monitor) -> None:
        """Fuse a ``DriftMonitor``'s per-poll tick into this fleet's
        dispatch.  The monitor must share this fleet's lane order; its
        state/params ride as traced tick inputs (mesh padding added here),
        and post-tick lanes flow back via ``monitor.absorb_fused``."""
        if list(monitor.cam_ids) != self.cam_ids:
            raise ValueError("drift monitor lane order != fleet lane order")
        monitor.bind_fused(self)
        self._drift = monitor
        self._drift_window = monitor.config.window
        pad = self._n_padded - self.n_lanes
        if pad:
            pad_state = drift_init(pad, monitor.config.window)
            pad_params = DriftParams.from_config(monitor.config, pad)
            self._drift_params = jax.tree_util.tree_map(
                lambda a, b: jnp.concatenate([a, b]),
                monitor.params, pad_params)
            self._drift_pad_state = pad_state
        else:
            self._drift_params = monitor.params
            self._drift_pad_state = None

    def _drift_inputs(self, errs, valid):
        """(state, errs, valid) for the tick, mesh-padded when needed."""
        pad = self._n_padded - self.n_lanes
        if self._drift is None:
            return (self._drift_state,
                    np.zeros(self._n_padded, np.float32),
                    np.zeros(self._n_padded, bool))
        state = self._drift.state
        if errs is None:
            errs = np.zeros(self.n_lanes, np.float32)
            valid = np.zeros(self.n_lanes, bool)
        if pad:
            state = jax.tree_util.tree_map(
                lambda a, b: jnp.concatenate([a, b]),
                state, self._drift_pad_state)
            errs = np.concatenate(
                [np.asarray(errs, np.float32), np.zeros(pad, np.float32)])
            valid = np.concatenate([np.asarray(valid, bool),
                                    np.zeros(pad, bool)])
        return state, errs, valid

    def cache_size(self) -> int:
        """Compiled-variant count of the fused tick (1 = no recompiles)."""
        return self._tick_jit._cache_size()

    @property
    def budget_scale(self) -> float:
        """The admission-control cap currently applied to every lane."""
        return self._budget_scale

    def set_budget_scale(self, scale: float) -> None:
        """Fleet-level wire-budget reallocation (admission control's
        degradation knob): cap every lane's nominal operating size at
        ``scale`` x the regression nominal.  A pure params-LEAF write --
        values change, shapes don't -- so the compiled tick's cache stays
        at one; degrading (or restoring) a tenant under oversubscription
        costs the same single dispatch as a quiet poll."""
        s = float(np.float32(scale))
        if not 0.0 < s <= 1.0:
            raise ValueError(f"budget_scale must be in (0, 1], got {scale}")
        if s == self._budget_scale:
            return
        self._budget_scale = s
        self.params.budget_scale = jnp.full_like(self.params.budget_scale, s)

    def __len__(self) -> int:
        return len(self._cams)

    def export_lane(self, camera_id: str) -> tuple[float, int]:
        """Write one lane's live PI state back into the camera's host
        controller and return it.

        In fleet mode the stacked lanes -- not the host fields -- own the
        live integral/operating point, so a camera leaving this fleet (herd
        migration hands it to another broker) must carry its lane state out
        through the host controller: the receiving fleet's ``_build_stack``
        seeds from exactly these fields, so the PI integral survives the
        hand-off with no retrace on either side (this is a host-side array
        read + two float writes; the compiled tick is untouched)."""
        i = self.lane_of[camera_id]
        ctl = self._cams[i].controller
        integral = float(self.state.integral[i])
        current = int(self.state.current_idx[i])
        ctl.integral = integral
        ctl._current = current
        return integral, current

    # -- live reconfiguration ------------------------------------------------
    def sync(self) -> tuple[list[int], list[int]]:
        """Fold per-camera retargets / table refreshes into the stack.

        Called at the top of every ``decide``; O(N) integer compares when
        nothing changed.  A retarget rewrites the camera's params lane and
        mirrors the host's state reset (integral, re-seeded operating
        point); a table refresh hot-swaps the camera's table lane and
        re-seeds the operating point while the integral carries over --
        exactly the host-side ``set_target`` / ``swap_table`` contracts.

        Returns ``(table_swapped, retargeted)`` lane indices -- the exact
        set of lanes rewritten this sync (empty when nothing changed),
        which is how the drift-refresh tests assert that an
        auto-recharacterization touched precisely the fired cameras.
        """
        table_swapped = [cam.table_version != self._table_versions[i]
                         for i, cam in enumerate(self._cams)]
        retargeted = [cam.qos_version != self._qos_versions[i]
                      for i, cam in enumerate(self._cams)]
        need = max(len(c.controller.table.settings) for c in self._cams)
        if need > self.capacity:
            # at least one refreshed table outgrew the shared padding: grow
            # to the whole fleet's requirement at once and rebuild the
            # stack -- ONE deliberate recompile.  The fleet lanes, not the
            # (stale in fleet mode) host fields, own the live PI state, so
            # it is carried across the rebuild; changed lanes re-seed below.
            self.capacity = need
            state = self.state
            self._build_stack()
            self.state = state
        else:
            for i, cam in enumerate(self._cams):
                ctl = cam.controller
                if table_swapped[i]:
                    fresh = JaxControllerTables.from_table(
                        ctl.table, capacity=self.capacity)
                    self.tables = fleet_swap_tables(self.tables, i, fresh)
                    self._table_versions[i] = cam.table_version
                if retargeted[i]:
                    self.params = _set_lane(
                        self.params, i, ControllerParams.from_controller(
                            ctl, budget_scale=self._budget_scale,
                            tier=self._tier))
                    self._qos_versions[i] = cam.qos_version
                    self._targets[i] = ctl.config.latency_target
        for i, cam in enumerate(self._cams):
            if not (table_swapped[i] or retargeted[i]):
                continue
            ctl = cam.controller
            # mirror the host contracts: both paths re-seed the operating
            # point; only a RETARGET resets the integral (``set_target``)
            # -- a bare table swap carries it (``swap_table``: the network
            # didn't reset with the tables)
            integral = (self.state.integral.at[i].set(ctl.integral)
                        if retargeted[i] else self.state.integral)
            self.state = ControllerState(
                integral=integral,
                current_idx=self.state.current_idx.at[i].set(ctl._current),
                feasible=self.state.feasible,
                last_error=self.state.last_error)
        return ([i for i, s in enumerate(table_swapped) if s],
                [i for i, r in enumerate(retargeted) if r])

    # -- the fused fleet tick ------------------------------------------------
    def _dispatch(self, lat_eff, drift_errs, drift_valid):
        """Run the ONE compiled dispatch and absorb its state."""
        dstate, derrs, dvalid = self._drift_inputs(drift_errs, drift_valid)
        operands = (self.state, dstate, lat_eff, derrs, dvalid,
                    self.tables, self.params, self._drift_params)
        if self._sharding is not None:
            # normalize operand placement onto the lane sharding: a no-op
            # for the fed-back sharded state, a cheap host->device transfer
            # (which jit would pay anyway) for per-poll numpy inputs --
            # keeps the dispatch signature, and so cache_size(), at one.
            # The placed stacks are kept so later polls skip the transfer.
            operands = jax.device_put(operands, self._sharding)
            (self.state, _, _, _, _, self.tables, self.params,
             self._drift_params) = operands
        new_ctrl, new_drift, aux = self._tick_jit(*operands)
        self.state = new_ctrl
        with TraceAnnotation("mez.fleet_tick.wait"):
            aux = jax.device_get(aux)
        fired_cams: list[str] = []
        if self._drift is not None:
            fired_cams = self._drift.absorb_fused(
                new_drift, aux.fired, aux.score)
        return aux, fired_cams

    # mezlint: poll-path
    def tick(self, lat, valid, drift_errs=None, drift_valid=None, *,
             record: bool = True) -> FleetTickResult:
        """One fused control+drift tick for the whole fleet.

        ``lat``/``valid`` are lane-ordered arrays: observed p95 latency
        (seconds) and whether the lane actually has samples this poll.
        Invalid lanes are fed their own latency target (zero error ->
        in-band hold, state untouched), so a single compiled dispatch
        still covers every camera.  ``drift_errs``/``drift_valid`` feed the
        fused drift tick when a monitor is attached (None -> no drift
        observation this poll).

        Returns a lazy :class:`FleetTickResult`; its ``fired_cams`` lists
        the drift lanes that crossed ``hi`` this tick, in lane order.  The
        host recharacterizes those, then calls :meth:`retick` to re-decide
        against the refreshed tables -- same compiled callable, cache
        stays at one.
        """
        self.sync()
        lat = np.asarray(lat, np.float32)
        valid = np.asarray(valid, bool)
        pad = self._n_padded - self.n_lanes
        if pad:
            lat = np.concatenate([lat, np.zeros(pad, np.float32)])
            valid = np.concatenate([valid, np.zeros(pad, bool)])
        lat_eff = np.where(valid, lat, self._targets)
        self._pre_state = self.state
        aux, fired_cams = self._dispatch(lat_eff, drift_errs, drift_valid)
        self._last_lat_eff = lat_eff
        if record and self.record_history:
            n = self.n_lanes
            self.history.append({
                "lat": lat_eff[:n].tolist(), "fed": valid[:n].tolist(),
                "idx": np.asarray(aux.step.idx)[:n].tolist(),
                "acted": np.asarray(aux.step.acted)[:n].tolist(),
                "feasible": np.asarray(aux.step.feasible)[:n].tolist(),
                "table_versions": list(self._table_versions),
            })
        return FleetTickResult(self.cam_ids, self.lane_of, aux, fired_cams)

    def retick(self) -> FleetTickResult:
        """Re-decide the tick just taken, against freshly swapped tables.

        Restores the pre-tick controller state, folds the host-side
        refreshes in via ``sync()`` (which re-seeds the swapped lanes,
        mirroring the unfused refresh-before-decide ordering), and
        re-dispatches the SAME compiled tick with a no-op drift
        observation: fired lanes were cleared+disarmed by the first
        dispatch (cannot refire on an empty window) and rearmed lanes are
        already armed, so the drift state is provably unchanged.
        """
        if self._pre_state is None:
            raise RuntimeError("retick() without a preceding tick()")
        self.state = self._pre_state
        self.sync()
        aux, _ = self._dispatch(self._last_lat_eff, None, None)
        if self.record_history and self.history:
            n = self.n_lanes
            row = self.history[-1]
            row["idx"] = np.asarray(aux.step.idx)[:n].tolist()
            row["acted"] = np.asarray(aux.step.acted)[:n].tolist()
            row["feasible"] = np.asarray(aux.step.feasible)[:n].tolist()
            row["table_versions"] = list(self._table_versions)
        return FleetTickResult(self.cam_ids, self.lane_of, aux, [])

    def decide(self, feedback) -> dict[str, ControlDecision]:
        """Dict-compat wrapper over :meth:`tick`.

        ``feedback`` maps camera_id -> observed p95 latency (seconds), or
        None for cameras with no samples yet.  Returns one host-shaped
        ``ControlDecision`` per camera (every lane materialized).
        """
        n = self.n_lanes
        lat = np.zeros(n, np.float32)
        valid = np.zeros(n, bool)
        for i, cid in enumerate(self.cam_ids):
            f = feedback.get(cid)
            if f is not None:
                valid[i] = True
                lat[i] = f
        return dict(self.tick(lat, valid))
