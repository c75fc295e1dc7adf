"""What every cell's run shares: the benchmark description, the device
check, the compile cache, the profiler window, the metric readers and the
result line."""

from __future__ import annotations

import dataclasses
import importlib.util
import json
import os
import sys
import tempfile
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


class NoChip(RuntimeError):
    """JAX found no TPU, or fewer chips than the cell asks for."""


@dataclasses.dataclass
class Cell:
    """One ``workloads`` entry with its configuration and traffic files."""
    workload: dict
    config: dict
    traffic: dict
    metrics: list[dict]          # every metric that applies to this cell
    seed: int
    seconds: float
    trace: bool

    @property
    def name(self) -> str:
        return self.workload["name"]


def load_cell(name: str, seed: int, seconds: float, trace: bool) -> Cell:
    with open(os.path.join(ROOT, "BENCHMARK.json")) as fh:
        bench = json.load(fh)
    work = next((w for w in bench["workloads"] if w["name"] == name), None)
    if work is None:
        raise KeyError(f"no workload named {name!r} in BENCHMARK.json")
    conf = next(c for c in bench["configs"] if c["name"] == work["config"])
    with open(os.path.join(ROOT, conf["file"])) as fh:
        config = json.load(fh)
    with open(os.path.join(HERE, "traffic", work["traffic"] + ".json")) as fh:
        traffic = json.load(fh)
    group = "per_layer" if trace else "end_to_end"
    metrics = [m for m in bench[group]
               if name in m.get("workloads", [name])]
    return Cell(work, config, traffic, metrics, seed, seconds, trace)


def enable_compile_cache() -> str:
    """JAX's persistent compilation cache at a fixed path in the checkout
    (``JAX_COMPILATION_CACHE_DIR`` wins when set); every program is kept,
    however quick it was to compile, so a second run compiles nothing."""
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or os.path.join(
        ROOT, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return path


def check_device(chips: int, require_tpu: bool = True) -> dict:
    """The device as JAX reports it; raises ``NoChip`` before any work when
    it is not a TPU or has fewer chips than asked for."""
    import jax

    devs = jax.devices()
    info = {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}
    if require_tpu and info["platform"] != "tpu":
        raise NoChip(f"no TPU: JAX found {info['platform']!r} devices")
    if require_tpu and len(devs) < chips:
        raise NoChip(f"the cell needs {chips} chips, JAX found {len(devs)}")
    peaks = load_peaks()
    if require_tpu and info["kind"] not in peaks:
        raise NoChip(f"device kind {info['kind']!r} is not in peaks.json")
    return info


def load_peaks() -> dict:
    with open(os.path.join(HERE, "peaks.json")) as fh:
        return json.load(fh)["devices"]


def memory_peak_bytes() -> int | None:
    import jax

    peaks = []
    for d in jax.local_devices():
        stats = d.memory_stats() or {}
        if "peak_bytes_in_use" in stats:
            peaks.append(int(stats["peak_bytes_in_use"]))
    return max(peaks) if peaks else None


class CompileCounter:
    """Counts XLA backend compilations while armed (none may happen inside
    a measured window)."""

    def __init__(self):
        import jax.monitoring

        self.armed = False
        self.count = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, duration: float, **_):
        if self.armed and event == "/jax/core/compile/backend_compile_duration":
            self.count += 1


class Window:
    """The measured window: its host-clock bounds and, with ``trace``, a
    profiler trace of it reduced to a ``trace_reduce.Summary``."""

    def __init__(self, trace: bool):
        self.trace = trace
        self.summary = None
        self._dir = None

    def __enter__(self) -> "Window":
        import jax

        if self.trace:
            self._dir = tempfile.TemporaryDirectory(prefix="mezbench-trace-")
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.host_tracer_level = 2
            jax.profiler.start_trace(self._dir.name, profiler_options=opts)
            self._span = jax.profiler.TraceAnnotation("mezbench.window")
            self._span.__enter__()
        self.t0 = time.perf_counter()
        return self

    def __exit__(self, *exc) -> None:
        import jax

        self.t1 = time.perf_counter()
        if self.trace:
            self._span.__exit__(None, None, None)
            jax.profiler.stop_trace()

    @property
    def seconds(self) -> float:
        return self.t1 - self.t0

    def reduce(self):
        """Reduce the recorded trace (once) and delete it."""
        if self._dir is None:
            return None
        from . import trace_reduce

        try:
            self.summary = trace_reduce.reduce_dir(self._dir.name)
        finally:
            self._dir.cleanup()
            self._dir = None
        return self.summary


def read_metrics(cell: Cell, run) -> dict:
    """Each metric of the cell through its own reader,
    ``metrics/<name>.py``; a reader that finds nothing returns None and
    the metric is left out."""
    out = {}
    for m in cell.metrics:
        path = os.path.join(HERE, "metrics", m["name"] + ".py")
        spec = importlib.util.spec_from_file_location(
            "mezbench_metric_" + m["name"].replace(".", "_"), path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        value = mod.read(run)
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


def emit(result: dict, checks: dict) -> None:
    """Print each compared number beside its limit as the last lines of
    standard error, and the result as the last line of standard output
    with the checks under the last key."""
    for name, c in checks.items():
        print(f"check {name} = {c['value']} ({c['keep']} {c['limit']})",
              file=sys.stderr, flush=True)
    result = dict(result)
    result["checks"] = checks
    print(json.dumps(result), flush=True)
