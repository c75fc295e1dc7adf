"""Reduce a profiler trace (``.xplane.pb``) of one measured window.

From the device planes (``/device:TPU:n``): the union of the intervals in
which an XLA op ran (busy time, averaged over the chips that ran any), the
device time and launch count of every XLA module by name (a jitted
function's module is ``jit_<name>``), and the device time of every op name.
From the host planes: the benchmark's own spans (``mezbench.*``), which
label each idle gap of the device by what the host was doing in it.
"""

from __future__ import annotations

import dataclasses
import glob
import os

SPAN_PREFIX = "mezbench."
WINDOW_SPAN = "mezbench.window"      # the whole measured window


@dataclasses.dataclass
class Summary:
    devices: int                        # device planes that ran an op
    busy_s: float                       # device busy time (union of ops)
    window_s: float                     # traced window length
    modules: dict[str, list]            # module name -> [seconds, launches]
    ops: dict[str, float]               # op name and shape -> seconds
    gaps: list[tuple[str, float]]       # the 10 longest idle gaps:
                                        # (host span, seconds)

    def module_seconds(self, pattern: str) -> tuple[float, int]:
        """Device seconds and launches of the modules whose name contains
        ``pattern``."""
        sec = sum(v[0] for k, v in self.modules.items() if pattern in k)
        n = sum(v[1] for k, v in self.modules.items() if pattern in k)
        return sec, n

    def breakdown(self) -> dict:
        top = sorted(self.ops.items(), key=lambda kv: -kv[1])[:10]
        return {"device_ops": [[k, v] for k, v in top],
                "idle_gaps": [[k, v] for k, v in self.gaps[:10]]}


def _union(intervals):
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return out


def reduce_file(path: str, window: tuple[int, int] | None = None) -> Summary:
    """``window`` (start, end) in the trace's nanoseconds clips the device
    timeline; by default it is the benchmark's ``mezbench.window`` span."""
    import jax

    pd = jax.profiler.ProfileData.from_file(path)
    spans = []
    devices = []
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:") or \
                plane.name.startswith("/device:GPU:"):
            devices.append(plane)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(SPAN_PREFIX):
                        spans.append((ev.start_ns,
                                      ev.start_ns + ev.duration_ns, ev.name))
    if window is None:
        whole = [(s, e) for s, e, n in spans if n == WINDOW_SPAN]
        if whole:
            window = whole[0]
        elif spans:
            window = (min(s for s, _, _ in spans), max(e for _, e, _ in spans))
        else:
            window = (0, 0)
    w0, w1 = window
    modules: dict[str, list] = {}
    ops: dict[str, float] = {}
    busy_total = 0.0
    n_dev = 0
    raw_gaps: list[tuple[float, float]] = []
    for plane in devices:
        intervals = []
        for line in plane.lines:
            if line.name == "XLA Modules":
                for ev in line.events:
                    m = modules.setdefault(ev.name.split("(")[0], [0.0, 0])
                    m[0] += ev.duration_ns * 1e-9
                    m[1] += 1
            elif line.name == "XLA Ops":
                for ev in line.events:
                    key = _short(ev.name)
                    ops[key] = ops.get(key, 0.0) + ev.duration_ns * 1e-9
                    s = max(ev.start_ns, w0)
                    e = min(ev.start_ns + ev.duration_ns, w1)
                    if e > s:
                        intervals.append((s, e))
        if not intervals:
            continue
        n_dev += 1
        merged = _union(intervals)
        busy_total += sum(e - s for s, e in merged) * 1e-9
        edges = [w0] + [x for iv in merged for x in iv] + [w1]
        raw_gaps += [(g0, g1) for g0, g1 in zip(edges[0::2], edges[1::2])
                     if g1 > g0]
    raw_gaps.sort(key=lambda g: g[0] - g[1])
    gaps = [(_label(spans, (g0 + g1) / 2), (g1 - g0) * 1e-9)
            for g0, g1 in raw_gaps[:10]]
    return Summary(devices=n_dev, busy_s=busy_total / max(n_dev, 1),
                   window_s=(w1 - w0) * 1e-9, modules=modules, ops=ops,
                   gaps=gaps)


def _short(op: str) -> str:
    """An HLO op's name and result shape, e.g. ``%while (s32[480,144,256]``,
    from its full instruction text."""
    head, _, rest = op.partition(" = ")
    if not rest:
        return op[:64]
    return (head + " " + rest.split("{")[0].split(" ")[0])[:64]


def _label(spans, t: float) -> str:
    """The innermost benchmark span covering time ``t``."""
    best = None
    for s, e, name in spans:
        if name != WINDOW_SPAN and s <= t <= e and \
                (best is None or e - s < best[0]):
            best = (e - s, name)
    return best[1] if best else "outside spans"


def reduce_dir(directory: str) -> Summary:
    files = glob.glob(os.path.join(directory, "**", "*.xplane.pb"),
                      recursive=True)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {directory}")
    return reduce_file(max(files, key=os.path.getmtime))
