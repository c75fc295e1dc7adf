"""The program's own spans in a profiler trace, on the clock of the device
timeline that ``trace_reduce`` reads.

The program names each layer boundary of the served path and of
characterization with a ``jax.profiler.TraceAnnotation`` called
``mez.<layer>`` (PERF.md lists them).  From the host planes of one
``.xplane.pb``: every ``mez.*`` event, clipped to the measured window (the
benchmark's ``mezbench.window`` span), nested by interval containment on
its own host thread.  Per span name: total seconds, count, self seconds
(the duration less the time its direct children cover) and the names of
its direct parents.  The device's ten longest idle gaps, the ones
``trace_reduce`` finds, are labelled with the innermost benchmark span, a
``/`` and the innermost program span open at their midpoint
(``mezbench.poll/mez.fleet_tick.wait``); a gap no program span covers
keeps the benchmark span's label alone.
"""

from __future__ import annotations

import dataclasses
import glob
import os

from . import trace_reduce

PROGRAM_PREFIX = "mez."
TOP = ""                    # the parent name of a span no other span holds


@dataclasses.dataclass
class SpanStats:
    seconds: float = 0.0                # total duration
    count: int = 0
    self_s: float = 0.0                 # duration less direct children's
    parents: set[str] = dataclasses.field(default_factory=set)


@dataclasses.dataclass
class Spans:
    stats: dict[str, SpanStats]         # span name -> its numbers
    gaps: list[tuple[str, float]]       # the 10 longest idle gaps:
                                        # (label, seconds)

    def table(self) -> dict:
        """``{name: [seconds, count, self seconds, [parents]]}``."""
        return {k: [v.seconds, v.count, v.self_s, sorted(v.parents)]
                for k, v in sorted(self.stats.items())}


def span_stats(lines, window: tuple[int, int]) -> dict[str, SpanStats]:
    """Nest each host thread's program events and sum them by name.

    ``lines``: per host thread, its ``(start_ns, end_ns, name)`` program
    events.  An event is clipped to ``window``; its parent is the
    innermost earlier event of the same thread that contains it."""
    w0, w1 = window
    stats: dict[str, SpanStats] = {}

    def close(node):
        s, e, name, children = node
        covered = sum(b - a for a, b in trace_reduce._union(children))
        st = stats[name]
        st.seconds += (e - s) * 1e-9
        st.count += 1
        st.self_s += (e - s - covered) * 1e-9

    for events in lines:
        clipped = sorted(((max(s, w0), min(e, w1), n) for s, e, n in events
                          if s < w1 and e > w0),
                         key=lambda ev: (ev[0], -ev[1]))
        stack: list[list] = []
        for s, e, name in clipped:
            while stack and not (stack[-1][0] <= s and e <= stack[-1][1]):
                close(stack.pop())
            if stack:
                stack[-1][3].append((s, e))
            stats.setdefault(name, SpanStats()).parents.add(
                stack[-1][2] if stack else TOP)
            stack.append([s, e, name, []])
        while stack:
            close(stack.pop())
    return stats


def label(bench, program, t: float) -> str:
    """What the host was doing at time ``t``: ``trace_reduce``'s label (the
    innermost benchmark span), then ``/`` and the innermost program span
    where one is open."""
    outer = trace_reduce._label(bench, t)
    inner = [(e - s, name) for s, e, name in program if s <= t <= e]
    return f"{outer}/{min(inner)[1]}" if inner else outer


def _idle_gaps(planes, w0: int, w1: int, n: int = 10):
    """The ``n`` longest idle intervals of the device planes in the window,
    found as ``trace_reduce.reduce_file`` finds them."""
    gaps = []
    for plane in planes:
        if not plane.name.startswith(("/device:TPU:", "/device:GPU:")):
            continue
        intervals = []
        for line in plane.lines:
            if line.name != "XLA Ops":
                continue
            for ev in line.events:
                s = max(ev.start_ns, w0)
                e = min(ev.start_ns + ev.duration_ns, w1)
                if e > s:
                    intervals.append((s, e))
        if not intervals:
            continue
        merged = trace_reduce._union(intervals)
        edges = [w0] + [x for iv in merged for x in iv] + [w1]
        gaps += [(g0, g1) for g0, g1 in zip(edges[0::2], edges[1::2])
                 if g1 > g0]
    gaps.sort(key=lambda g: g[0] - g[1])
    return gaps[:n]


def reduce_file(path: str) -> Spans:
    import jax

    pd = jax.profiler.ProfileData.from_file(path)
    bench, program, lines = [], [], []
    for plane in pd.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            mine = []
            for ev in line.events:
                iv = (ev.start_ns, ev.start_ns + ev.duration_ns, ev.name)
                if ev.name.startswith(trace_reduce.SPAN_PREFIX):
                    bench.append(iv)
                elif ev.name.startswith(PROGRAM_PREFIX):
                    mine.append(iv)
            if mine:
                lines.append(mine)
                program += mine
    whole = [(s, e) for s, e, n in bench if n == trace_reduce.WINDOW_SPAN]
    every = bench + program
    if whole:
        window = whole[0]
    elif every:
        window = (min(s for s, _, _ in every), max(e for _, e, _ in every))
    else:
        window = (0, 0)
    gaps = [(label(bench, program, (g0 + g1) / 2), (g1 - g0) * 1e-9)
            for g0, g1 in _idle_gaps(pd.planes, *window)]
    return Spans(stats=span_stats(lines, window), gaps=gaps)


def reduce_dir(directory: str) -> Spans:
    files = glob.glob(os.path.join(directory, "**", "*.xplane.pb"),
                      recursive=True)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {directory}")
    return reduce_file(max(files, key=os.path.getmtime))


def layer_numbers(spans: Spans, run) -> dict[str, float]:
    """Per-layer numbers of one run from its spans, where the run has them.

    Serving: ``session_self_ms`` (mean self time of ``mez.poll``),
    ``fleet_tick_host_us`` (mean ``mez.fleet_tick``), and ``fetch_ms``,
    ``transform_ms``, ``deflate_ms`` (total ``mez.fetch``,
    ``mez.transform``, ``mez.deflate`` over the frames shipped with a
    payload, ``run.delivered``).  Onboarding: ``char_host_ms``
    (``mez.char.boxes``, ``.calib`` and ``.score`` per sweep)."""
    st = spans.stats
    out = {}
    poll, tick = st.get("mez.poll"), st.get("mez.fleet_tick")
    if poll is not None:
        out["session_self_ms"] = poll.self_s / poll.count * 1e3
    if tick is not None:
        out["fleet_tick_host_us"] = tick.seconds / tick.count * 1e6
    shipped = getattr(run, "delivered", 0)
    for key, name in (("fetch_ms", "mez.fetch"),
                      ("transform_ms", "mez.transform"),
                      ("deflate_ms", "mez.deflate")):
        if shipped and name in st:
            out[key] = st[name].seconds / shipped * 1e3
    sweeps = getattr(run, "sweeps_s", None)
    host = [st[n].seconds for n in ("mez.char.boxes", "mez.char.calib",
                                    "mez.char.score") if n in st]
    if sweeps and host:
        out["char_host_ms"] = sum(host) / len(sweeps) * 1e3
    return out
