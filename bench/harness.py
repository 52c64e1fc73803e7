"""What every cell shares: the benchmark's definition, the device check,
the compile cache, the compile counter, the traced window and the result
line.

Nothing here knows a cell.  ``run.py`` resolves ``--workload`` through
``BENCHMARK.json`` to a configuration file, a traffic file and the driver
that the traffic file names (``drivers/<driver>.py``), and the per-layer
metrics through ``metrics/<name>.py``.  A later cell that reuses a driver
adds data files only.
"""

from __future__ import annotations

import importlib.util
import json
import math
import os
import sys
import time
from dataclasses import dataclass, field
from typing import Any, Callable, Optional

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
#: process start as run.py records it; set-up runs from here to the window
START = time.perf_counter()


def setup_seconds() -> float:
    return time.perf_counter() - START


class BenchError(RuntimeError):
    """The run cannot produce a result (no chip, unknown cell, bad file)."""


# ---------------------------------------------------------------------------
# definition
# ---------------------------------------------------------------------------


def load_benchmark(root: str = ROOT) -> dict:
    path = os.path.join(root, "BENCHMARK.json")
    if not os.path.exists(path):
        raise BenchError(f"no BENCHMARK.json at {root}")
    with open(path) as f:
        return json.load(f)


def load_json(rel: str, root: str = ROOT) -> dict:
    path = os.path.join(root, rel)
    if not os.path.exists(path):
        raise BenchError(f"missing file {rel}")
    with open(path) as f:
        return json.load(f)


def load_module(rel: str, root: str = ROOT):
    """Import a benchmark file by its path (names may hold '-' and '.')."""
    path = os.path.join(root, rel)
    if not os.path.exists(path):
        raise BenchError(f"missing file {rel}")
    name = "bench_" + rel.replace("/", "_").replace("-", "_") \
        .replace(".", "_")
    if name in sys.modules:
        return sys.modules[name]
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod
    spec.loader.exec_module(mod)
    return mod


@dataclass
class Cell:
    """One workload of BENCHMARK.json with everything it resolves to."""

    name: str
    chips: int
    config_name: str
    config: dict                  # the configuration file
    traffic: dict                 # the traffic file
    end_to_end: list              # metric entries this cell reports
    per_layer: list

    @property
    def driver(self) -> str:
        return self.traffic["driver"]


def traffic_file(traffic: str) -> str:
    return f"bench/traffic/{traffic}.json"


def driver_file(driver: str) -> str:
    return f"bench/drivers/{driver}.py"


def metric_file(metric: str) -> str:
    return f"bench/metrics/{metric}.py"


def _applies(metric: dict, cell: str, e2e_names: set) -> bool:
    if "workloads" in metric:
        return cell in metric["workloads"]
    if "moves" in metric:                       # a per-layer metric
        return metric["moves"] in e2e_names
    return True


def resolve_cell(bench: dict, name: str, root: str = ROOT) -> Cell:
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise BenchError(f"unknown workload {name!r}; known: "
                         f"{sorted(cells)}")
    w = cells[name]
    configs = {c["name"]: c for c in bench["configs"]}
    cfg_entry = configs[w["config"]]
    e2e = [m for m in bench["end_to_end"] if _applies(m, name, set())]
    e2e_names = {m["name"] for m in e2e}
    per_layer = [m for m in bench["per_layer"]
                 if _applies(m, name, e2e_names)]
    return Cell(name=name, chips=int(w["chips"]),
                config_name=w["config"],
                config=load_json(cfg_entry["file"], root),
                traffic=load_json(traffic_file(w["traffic"]), root),
                end_to_end=e2e, per_layer=per_layer)


# ---------------------------------------------------------------------------
# device, cache, compile counter
# ---------------------------------------------------------------------------


def enable_compile_cache(root: str = ROOT) -> str:
    """JAX's persistent cache at a fixed path inside the checkout, unless
    ``JAX_COMPILATION_CACHE_DIR`` names one (the program then takes that
    one too).  Call before JAX is imported."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = os.path.join(root, ".jax_cache")
        os.environ["JAX_COMPILATION_CACHE_DIR"] = path
    import jax
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def require_devices(chips: int):
    """The first ``chips`` TPU devices; anything else is an error."""
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise BenchError(f"needs a TPU, found platform "
                         f"{devs[0].platform!r} ({len(devs)} device(s))")
    if len(devs) < chips:
        raise BenchError(f"needs {chips} TPU devices, found {len(devs)}")
    return devs[:chips]


class CompileCounter:
    """Counts XLA compiles and loads from the persistent cache, from
    JAX's own monitoring events, so that either inside the window shows."""

    EVENTS = ("/jax/core/compile/backend_compile_duration",
              "/jax/compilation_cache/cache_retrieval_time_sec")

    def __init__(self):
        import jax
        self.count = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, duration: float, **kwargs) -> None:
        if event in self.EVENTS:
            self.count += 1


def device_info(devs) -> dict:
    peaks = []
    for d in devs:
        stats = d.memory_stats() or {}
        peaks.append(int(stats.get("peak_bytes_in_use", 0)))
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs), "memory_peak_bytes": max(peaks)}


def load_peaks(device_kind: str) -> dict:
    table = load_json("bench/peaks.json")
    kinds = table["kinds"]
    if device_kind not in kinds:
        raise BenchError(f"no peaks for device kind {device_kind!r} in "
                         f"bench/peaks.json; known: {sorted(kinds)}")
    return kinds[device_kind]


# ---------------------------------------------------------------------------
# what a driver hands back
# ---------------------------------------------------------------------------


@dataclass
class Check:
    """One number compared against its limit: the run is correct only
    where ``value <= limit`` (and the value is a finite number)."""

    name: str
    value: float
    limit: float

    @property
    def ok(self) -> bool:
        return (isinstance(self.value, (int, float))
                and math.isfinite(self.value) and self.value <= self.limit)


@dataclass
class Outcome:
    attempted: int
    failed: int
    checks: list                          # of Check
    end_to_end: dict                      # name -> value
    facts: dict = field(default_factory=dict)   # for the per-layer readers
    trace: Any = None                     # trace.Summary of a traced run
    device: Optional[dict] = None         # device_info() as the window closed

    @property
    def correct(self) -> bool:
        return bool(self.checks) and self.failed == 0 \
            and all(c.ok for c in self.checks)


def read_per_layer(cell: Cell, outcome: Outcome, root: str = ROOT) -> dict:
    """Each per-layer metric from its own reader; a reader that finds
    nothing returns None and the metric is left out."""
    out = {}
    for m in cell.per_layer:
        reader = load_module(metric_file(m["name"]), root)
        value = reader.read(outcome.facts, outcome.trace)
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


def result_line(cell: Cell, outcome: Outcome, devs, trace: bool) -> dict:
    if trace:
        metrics = read_per_layer(cell, outcome)
    else:
        metrics = {}
        for m in cell.end_to_end:
            if m["name"] not in outcome.end_to_end:
                raise BenchError(f"driver did not report {m['name']}")
            metrics[m["name"]] = {"value": float(outcome.end_to_end[
                m["name"]]), "unit": m["unit"]}
    line = {"correct": outcome.correct, "attempted": outcome.attempted,
            "failed": outcome.failed, "metrics": metrics,
            "device": dict(outcome.device or device_info(devs))}
    if trace and outcome.trace is not None:
        line["device"]["busy_s"] = outcome.trace.busy_s
        line["device"]["window_s"] = outcome.trace.window_s
        line["breakdown"] = outcome.trace.breakdown()
    line["compared"] = {c.name: {"value": c.value, "limit": c.limit}
                        for c in outcome.checks}
    return line


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


# ---------------------------------------------------------------------------
# the measured window
# ---------------------------------------------------------------------------


def run_window(seconds: float, one: Callable[[int], int]) -> tuple:
    """Call ``one(i)`` back to back; the window closes at the first
    completion after ``seconds``.  ``one`` returns the work it completed
    and waits for its result.  Returns (calls, work, elapsed seconds)."""
    calls = work = 0
    t0 = time.perf_counter()
    while True:
        work += one(calls)
        calls += 1
        elapsed = time.perf_counter() - t0
        if elapsed >= seconds:
            return calls, work, elapsed


@dataclass
class Window:
    setup_s: float
    calls: int
    work: int
    elapsed: float
    compiles: int                  # compiles and cache loads inside it
    device: dict                   # device_info() as it closed
    trace: Any                     # trace_reduce.Summary, or None


def measure(workload: str, seed: int, seconds: float, trace: bool, devs,
            one: Callable[[int], int], control=frozenset()) -> Window:
    """Set-up ends here: run the window (under the profiler when
    ``trace``) and read the device as it closes.  ``control``: the
    control-flow instructions of the program the window runs, as
    ``trace_reduce.reduce`` takes them."""
    counter = CompileCounter()
    setup_s, summary = setup_seconds(), None
    if trace:
        summary, (calls, work, elapsed) = traced(
            workload, seed, lambda: run_window(seconds, one), control)
    else:
        calls, work, elapsed = run_window(seconds, one)
    return Window(setup_s, calls, work, elapsed, counter.count,
                  device_info(devs), summary)


def trace_dir(workload: str, seed: int) -> str:
    base = os.environ.get("TMPDIR") or os.path.join(ROOT, ".bench_tmp")
    return os.path.join(base, "bench_trace", f"{workload}-{seed}")


def traced(workload: str, seed: int, window: Callable[[], Any],
           control=frozenset()) -> tuple:
    """Run ``window()`` under the profiler inside the span ``window``;
    returns (trace.Summary, what window() returned).  The trace is read
    and deleted before this returns.  ``BENCH_KEEP_TRACE`` names a file
    to which the flattened events are saved first."""
    import shutil
    import jax
    import trace_reduce as T
    path = trace_dir(workload, seed)
    shutil.rmtree(path, ignore_errors=True)
    jax.profiler.start_trace(path)
    try:
        with jax.profiler.TraceAnnotation(T.WINDOW):
            out = window()
    finally:
        jax.profiler.stop_trace()
    try:
        events = T.load(path)
        keep = os.environ.get("BENCH_KEEP_TRACE")
        if keep:
            T.save(events, keep)
        return T.reduce(events, control), out
    finally:
        shutil.rmtree(path, ignore_errors=True)
