"""Plain reference of Mez's serving semantics, and the check of a served run.

* ``Channel``: the paper-calibrated 802.11ac latency law (Section 2,
  Tables 1-2) with its seeded log-normal jitter: the latency every shipped
  frame is charged, and so the controller's sensor input.
* ``Table``: the controller's two lookup tables (Algorithm 1) as plain
  arrays, with the wire-size proxy the pre-screen uses.
* ``PI``: Algorithm 1, one host controller per camera lane, in float64.
* ``check_serving``: replays a recorded run poll by poll and says, for each
  delivered frame of the sampled subscriptions, what Mez should have served:
  the knob5 drop decision against the camera's last shipped frame, the
  controller's setting with the pre-screen's step-downs, the payload bytes
  and their deflate size.  It also checks delivery order per camera
  (chronological, at most once, nothing skipped).
"""

from __future__ import annotations

import dataclasses

import numpy as np

from . import knobs as RK

# -- the channel --------------------------------------------------------------

BASE_RATE = 3.809e7
BASE_OVERHEAD = 8.237e-3
OVERHEAD_PEER = 1.0
C1, C2, GAMMA, SIZE_REF = 0.347, 0.204, 0.962, 970e3
FPS_REF, FPS_COEFF = 5.0, 0.02
DIST_REF, DIST_COEFF = 6.0, 0.011
JITTER_SIGMA = 0.18
WORKLOAD_SCALES = {"jaad": 970e3 / 90e3, "dukemtmc": 1740e3 / 90e3}

# fixed broker costs charged to every shipped frame (seconds)
PUBLISH_API_COST = 0.4e-3
SUBSCRIBE_API_COST = 0.6e-3
BROKER_PROC_COST = 0.9e-3
LOG_COPY_COST_PER_MB = 8.0e-3
PRESCREEN_SLACK = 1.25
PRESCREEN_MAX_CANDIDATES = 3
MIN_WIRE_BYTES = 16.0


@dataclasses.dataclass
class Channel:
    workload: str
    n_active: int

    @property
    def scale(self) -> float:
        return WORKLOAD_SCALES[self.workload]

    def mean_latency(self, size: float, fps: float, distance: float) -> float:
        n = self.n_active
        size = size * self.scale
        peers = max(0, n - 1)
        load = 1.0 + FPS_COEFF * (fps / FPS_REF - 1.0)
        term = (max(size, 1.0) / SIZE_REF) ** GAMMA
        cont = 1.0 + (C1 * peers + C2 * peers * peers) * term * load
        dist = 1.0 + DIST_COEFF * max(0.0, distance - DIST_REF)
        oh = BASE_OVERHEAD * (1.0 + OVERHEAD_PEER * (n - 1))
        return (oh + (size / BASE_RATE) * cont) * dist * 1.0

    def jitter(self, seed: int, n: int) -> np.ndarray:
        """The first ``n`` jitter factors of a channel seeded ``seed``."""
        rng = np.random.default_rng(seed)
        s = JITTER_SIGMA
        return np.asarray([rng.lognormal(mean=-0.5 * s * s, sigma=s)
                           for _ in range(n)])

    def regression(self, smin: float, smax: float, fps: float,
                   distance: float) -> tuple[float, float]:
        """(slope, intercept) of latency on size over 16 sizes."""
        sizes = np.linspace(smin, smax, 16)
        lats = np.asarray([self.mean_latency(float(s), fps, distance)
                           for s in sizes])
        a, b = np.polyfit(np.asarray(sizes, np.float64), lats, 1)
        return float(a), float(b)


# -- the tables and the control law ---------------------------------------------

# A correct table's wire sizes may lie this far, relative, from this
# float64 reference's: the program sums its proxy features in float32 on
# the device (the onboarding cells hold its sizes to the same limit,
# ``size_rel_err``).  Where a size, a budget or a proxy estimate falls
# within that band of a boundary, every outcome on either side is allowed.
SIZE_TOL = 5e-4


@dataclasses.dataclass
class Table:
    """Algorithm 1's two lookup tables over the kept settings, built from
    the reference characterization: sizes sorted ascending (stable), the
    prefix maximum of accuracy and the setting that reaches it, and the
    wire-size proxy."""
    settings: list[tuple[int, int, int, int, int]]
    sizes_sorted: np.ndarray
    best_acc: np.ndarray
    best_idx: np.ndarray
    acc_by_setting: np.ndarray
    size_by_setting: np.ndarray
    proxy: np.ndarray | None            # [3, 2, 8] coefficients

    @classmethod
    def from_kept(cls, kept: dict, coeffs: np.ndarray | None,
                  grid_order) -> "Table":
        settings = [tuple(s) for s in grid_order if tuple(s) in kept]
        sizes = np.asarray([kept[s][0] for s in settings], np.float64)
        accs = np.asarray([kept[s][1] for s in settings], np.float64)
        order = np.argsort(sizes, kind="stable")
        best_acc = np.empty(len(order))
        best_idx = np.empty(len(order), np.int64)
        run_best, run_idx = -1.0, -1
        for i, j in enumerate(order):
            if accs[j] > run_best:
                run_best, run_idx = accs[j], j
            best_acc[i], best_idx[i] = run_best, run_idx
        return cls(settings, sizes[order], best_acc, best_idx, accs, sizes,
                   coeffs)

    def _best(self, cand) -> set[int]:
        """What the prefix maximum may pick from ``cand``: the highest
        accuracy, the smallest size among equals (the lowest index among
        equal sizes), or an equal-accuracy setting within the band of it."""
        a = max(self.acc_by_setting[j] for j in cand)
        tied = sorted(j for j in cand if self.acc_by_setting[j] == a)
        least = min(self.size_by_setting[j] for j in tied)
        out, seen = set(), set()
        for j in tied:
            z = float(self.size_by_setting[j])
            if z <= least * (1.0 + 2.0 * SIZE_TOL) and z not in seen:
                seen.add(z)
                out.add(j)
        return out

    def query_set(self, size: float, band: float, *, low: bool = False,
                  high: bool = False) -> set[int]:
        """Every setting index the lookup may give for a size known to
        +-``band`` over sizes known to +-``SIZE_TOL``.  ``low``/``high``:
        the size was clipped to the table's least/greatest size, which
        every table then holds exactly."""
        z = self.size_by_setting
        if high:
            return self._best(range(len(z)))
        if low:
            return self._best([j for j in range(len(z))
                               if z[j] == self.sizes_sorted[0]])
        sure = [j for j in range(len(z))
                if z[j] * (1.0 + SIZE_TOL) <= size - band
                or z[j] == self.sizes_sorted[0]]
        maybe = [j for j in range(len(z))
                 if z[j] * (1.0 - SIZE_TOL) <= size + band and j not in sure]
        out: set[int] = set()
        for mask in range(1 << min(len(maybe), 8)):
            cand = sure + [maybe[i] for i in range(len(maybe)) if mask >> i & 1]
            if cand:
                out |= self._best(cand)
        if len(maybe) > 8:
            out |= set(maybe)
        return out

    def step_down_set(self, idx: int, floor: float, diff: int) -> set[int]:
        """Every next-smaller setting (same knob5 threshold, accuracy at
        least ``floor``) the pre-screen's walk may take; -1 when none may
        qualify."""
        z, size = self.size_by_setting, self.size_by_setting[idx]
        cand = [j for j in range(len(z)) if j != idx
                and self.settings[j][4] == diff
                and self.acc_by_setting[j] >= floor
                and z[j] < size * (1.0 + 2.0 * SIZE_TOL)]
        sure = [j for j in cand if z[j] < size * (1.0 - 2.0 * SIZE_TOL)]
        if not cand:
            return {-1}
        top = max(z[j] for j in sure) if sure else -1.0
        out = {j for j in cand if z[j] >= top * (1.0 - 2.0 * SIZE_TOL)}
        if not sure:
            out.add(-1)
        return out

    def predict(self, cs: int, nbytes: int, feats: np.ndarray,
                art: bool) -> float:
        x = np.concatenate([[float(nbytes)], np.asarray(feats, np.float64),
                            [1.0]])
        return float(max(x @ self.proxy[cs, int(art)], MIN_WIRE_BYTES))


@dataclasses.dataclass
class Decision:
    idx: int
    size: float
    band: float
    acted: bool
    feasible: bool


class PI:
    """Algorithm 1 for one camera lane: nominal size from the regression,
    PI correction of the latency error outside a +-10 ms band, table
    lookup, accuracy check.  The gains come from a regression over the
    table's size range; the lane also runs them over that range moved by
    +-``SIZE_TOL`` to bound its size, and keeps every setting index the
    lookup may have given (``current``) until a delivery tells them
    apart."""
    ERROR_THRESHOLD, ALPHA_P, ALPHA_I, INTEGRAL_CLIP = 0.010, 0.8, 0.25, 1.0

    def __init__(self, table: Table, regressions, target: float,
                 accuracy: float):
        self.t, self.target, self.accuracy = table, target, accuracy
        self.gains = [(-self.ALPHA_P / max(a, 1e-12),
                       -self.ALPHA_I / max(a, 1e-12),
                       max(0.0, (target - b) / max(a, 1e-12)))
                      for a, b in regressions]
        self.integral = 0.0
        self.current = self._lookup([g[2] for g in self.gains])[0]

    @property
    def nominal(self) -> float:
        return self.gains[0][2]

    def _lookup(self, sizes) -> tuple[set[int], float, float]:
        t = self.t
        lo, hi = float(t.sizes_sorted[0]), float(t.sizes_sorted[-1])
        size = sizes[0]
        band = max(abs(z - size) for z in sizes) + SIZE_TOL * abs(size)
        low = size + band <= lo * (1.0 - SIZE_TOL)
        high = size - band >= hi * (1.0 + SIZE_TOL)
        c = float(np.clip(size, lo, hi))
        return t.query_set(c, band, low=low, high=high), c, band

    def hold(self) -> list[Decision]:
        return [Decision(i, self.nominal, 0.0, False, i >= 0)
                for i in sorted(self.current)]

    def update(self, latency: float) -> list[Decision]:
        error = latency - self.target
        if not (error > self.ERROR_THRESHOLD or error < -self.ERROR_THRESHOLD):
            return self.hold()
        self.integral = float(np.clip(self.integral + error,
                                      -self.INTEGRAL_CLIP, self.INTEGRAL_CLIP))
        idxs, size, band = self._lookup(
            [nom + k1 * error + k2 * self.integral
             for k1, k2, nom in self.gains])
        if idxs:
            self.current = set(idxs)
        return [Decision(i, size, band, True,
                         self.t.acc_by_setting[i] >= self.accuracy)
                for i in sorted(idxs)]


# -- the check ----------------------------------------------------------------------


@dataclasses.dataclass
class Delivery:
    cam: int
    tick: int                           # the camera's frame index
    payload: np.ndarray | None          # None: dropped by knob5
    setting: tuple | None               # the served setting; None = raw
    wire: int


@dataclasses.dataclass
class Poll:
    sub: int
    number: int                          # this subscription's poll count
    deliveries: list[Delivery]
    published: int                       # ticks every camera had published


@dataclasses.dataclass
class Deployment:
    """What the check needs to know of the served deployment."""
    table: Table                         # the reference's own
    frames: list[list[np.ndarray]]       # [camera][tick]
    backgrounds: list[np.ndarray]
    channel: Channel
    channel_seed: int
    fps: float
    distance: float
    latency_target: float
    accuracy_target: float
    feedback_window: int
    log_capacity: int                    # frames a camera's log holds

    def regressions(self) -> list[tuple[float, float]]:
        """The controller's latency regression over the table's size range,
        and over that range moved by -+``SIZE_TOL``."""
        lo, hi = float(self.table.sizes_sorted[0]), \
            float(self.table.sizes_sorted[-1])
        return [self.channel.regression(lo * f, hi * f, self.fps,
                                         self.distance)
                for f in (1.0, 1.0 - SIZE_TOL, 1.0 + SIZE_TOL)]


def _outcomes(t: Table, d: Decision, frame, bg, floor: float, last,
              shipped_before, lower: bool) -> set:
    """Every outcome that decision ``d`` may lead to for ``frame``:
    ``"drop"`` (knob5 against the camera's last shipped frame) or a
    served setting after the pre-screen's walk.  A candidate's size
    estimate is the proxy's, or the exact deflate size when another
    subscription already shipped that very payload and Mez still holds it
    in its frame cache; an estimate within the tolerance of the budget
    allows both sides."""
    if d.idx < 0:
        return {None}
    setting = t.settings[d.idx]
    frac = RK.change_fraction(frame, last)
    thresh = RK.DIFF_THRESHOLDS[setting[4]]
    if thresh >= 0.0 and frac is not None and frac <= thresh:
        return {"drop"}
    if t.proxy is None or not d.acted or not d.feasible:
        return {setting}
    budget = d.size * PRESCREEN_SLACK
    slack = d.band * PRESCREEN_SLACK
    outcomes = set()

    def walk(idx, step):
        s = t.settings[idx]
        payload = RK.transform(frame, s, bg, lower=lower)
        est = t.predict(s[1], payload.nbytes, RK.proxy_features(payload),
                        s[3] > 0)
        room = SIZE_TOL * est + slack
        fits = {est - room <= budget, est + room <= budget}
        if shipped_before(s):
            fits.add(float(RK.wire_size(payload)) <= budget)
        last_step = step == PRESCREEN_MAX_CANDIDATES - 1
        if True in fits or last_step:
            outcomes.add(s)
        if False in fits and not last_step:
            for down in t.step_down_set(idx, floor, s[4]):
                if down < 0:
                    outcomes.add(s)
                else:
                    walk(down, step + 1)

    walk(d.idx, 0)
    return outcomes


def check_serving(dep: Deployment, polls: list[Poll], n_subs: int,
                  cams_of: list[list[int]], sample: set[int], *,
                  lower: bool = False) -> dict:
    """Replay ``polls`` (every poll of the run, in order) and count, over
    the deliveries of the subscriptions in ``sample``: ``settings_off``
    (drop decision or served setting not one that Mez's control law and
    pre-screen give over the reference's own table), ``payload_off``
    (payload or wire size not the transform of the served setting), and,
    over every subscription, ``order_off`` (a camera's frames not
    delivered in order, once each, without gaps; a tenant that fell more
    than the camera's log behind resumes at the oldest frame the log still
    holds, as at-most-once delivery allows).  ``lower`` computes the
    reference transforms in bfloat16: the control."""
    t = dep.table
    total_shipped = sum(1 for p in polls for d in p.deliveries
                        if d.payload is not None or d.wire > 0)
    jit = dep.channel.jitter(dep.channel_seed, total_shipped)
    k = 0
    last_sent: dict[int, np.ndarray | None] = {}
    shipped: set = set()
    regs = dep.regressions()
    lanes = {s: {c: PI(t, regs, dep.latency_target, dep.accuracy_target)
                 for c in cams_of[s]} for s in sample}
    windows = {s: {c: [] for c in cams_of[s]} for s in sample}
    next_tick = {(s, c): 0 for s in range(n_subs) for c in cams_of[s]}
    out = {"settings_off": 0, "payload_off": 0, "order_off": 0,
           "checked": 0, "ambiguous": 0, "evicted": 0}
    for p in polls:
        cams = sorted(cams_of[p.sub], key=lambda c: f"cam{c}")
        r = p.number % len(cams)
        order = cams[r:] + cams[:r]
        by_cam = {c: [] for c in cams}
        for d in p.deliveries:
            by_cam.setdefault(d.cam, []).append(d)
        counts = {len(v) for v in by_cam.values()}
        if len(counts) != 1 or set(by_cam) != set(cams):
            out["order_off"] += 1
        in_sample = p.sub in sample
        if in_sample:
            dec = {}
            for c in cams:
                w = windows[p.sub][c]
                pi = lanes[p.sub][c]
                dec[c] = pi.update(float(np.percentile(w, 95))) if w \
                    else pi.hold()
        oldest = p.published - dep.log_capacity
        for c in order:
            for d in sorted(by_cam.get(c, []), key=lambda d: d.tick):
                want = next_tick[(p.sub, c)]
                if want < oldest:
                    want = oldest
                    out["evicted"] += oldest - next_tick[(p.sub, c)]
                if d.tick != want:
                    out["order_off"] += 1
                next_tick[(p.sub, c)] = d.tick + 1
                frame = dep.frames[c][d.tick]
                bg = dep.backgrounds[c]
                ships = d.payload is not None or d.wire > 0
                if in_sample:
                    out["checked"] += 1
                    latency = _check_one(
                        dep, d, frame, bg, dec[c], lanes[p.sub][c],
                        last_sent.get(c), shipped, out, lower)
                    if ships:
                        latency += dep.channel.mean_latency(
                            float(d.wire), dep.fps, dep.distance) * jit[k]
                        w = windows[p.sub][c]
                        w.append(latency + BROKER_PROC_COST
                                 + SUBSCRIBE_API_COST)
                        del w[:-dep.feedback_window]
                if ships:
                    k += 1
                    if d.setting is not None:
                        last_sent[c] = frame
                        shipped.add((c, d.tick) + tuple(d.setting[:4]))
    return out


def _check_one(dep, d: Delivery, frame, bg, decs: list[Decision], lane: PI,
               last, shipped, out, lower: bool) -> float:
    """Check one delivery against every outcome the lane's possible
    decisions allow, keep the decisions that explain it, and return its
    latency before the network term (publish, modification and log-copy
    costs)."""
    t = dep.table
    dropped = d.payload is None and d.wire == 0
    seen = "drop" if dropped else d.setting
    allowed, explains = set(), set()
    for dec in decs:
        o = _outcomes(t, dec, frame, bg, dep.accuracy_target, last,
                      lambda s: (d.cam, d.tick) + tuple(s[:4]) in shipped,
                      lower)
        allowed |= o
        if seen in o:
            explains.add(dec.idx)
    if len(allowed) > 1:
        out["ambiguous"] += 1
    if explains:
        lane.current = explains
        decs[:] = [x for x in decs if x.idx in explains]
    else:
        out["settings_off"] += 1
    if dropped:
        return 0.0
    if d.payload is None:
        out["payload_off"] += 1
        return 0.0
    if d.setting is None:
        want = frame
        cost = 0.0
    else:
        want = RK.transform(frame, d.setting, bg, lower=lower)
        cost = RK.overhead_ms(d.setting) * 1e-3
    if d.wire != RK.wire_size(want) or d.payload.shape != want.shape \
            or not np.array_equal(d.payload, want):
        out["payload_off"] += 1
    return (PUBLISH_API_COST + cost
            + LOG_COPY_COST_PER_MB * (want.nbytes * dep.channel.scale / 1e6))
