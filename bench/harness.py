"""The benchmark's registry, window and result line.

Everything a cell needs is found by name from its entry in
``BENCHMARK.json``:

* the configuration: the entry's ``file`` (``bench/configs/<name>.json``),
  whose ``driver`` key names the driver;
* the traffic mix: ``bench/traffic/<traffic>.json``;
* the driver: ``bench/drivers/<driver>.py``, whose ``run(spec)`` serves the
  mix and returns a :class:`Result`;
* each per-layer metric: ``bench/metrics/<name>.py``, whose
  ``read(ctx)`` returns the value or ``None`` when the run has nothing for
  it to read.

A later cell, mix or metric is a new file and a new entry; no file here
changes.
"""
from __future__ import annotations

import contextlib
import importlib.util
import json
import os
import shutil
import sys
import time
from dataclasses import dataclass, field
from pathlib import Path
from typing import Any, Callable, Dict, List, Optional, Tuple

BENCH = Path(__file__).resolve().parent
REPO = BENCH.parent
# fixed paths inside the checkout: the compile cache's path is part of its
# key, so a directory that moved would never hit
CACHE_DIR = REPO / ".bench_cache"
# a traced run traces the first this many seconds of its window: a whole
# window of decode steps is millions of device events, and writing them
# out takes minutes
TRACE_SECONDS = 6.0


# ---------------------------------------------------------------------------
# Registry
# ---------------------------------------------------------------------------

def load_module(path: Path):
    """Import a file of the benchmark by path (names may hold dots)."""
    name = "bench_" + "_".join(path.relative_to(path.parents[1]).with_suffix(
        "").parts).replace(".", "_").replace("-", "_")
    spec = importlib.util.spec_from_file_location(name, path)
    if spec is None or spec.loader is None:
        raise ImportError(f"cannot load {path}")
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod
    spec.loader.exec_module(mod)
    return mod


@dataclass
class Cell:
    name: str
    entry: Dict[str, Any]          # the workloads entry
    config: Dict[str, Any]         # the configuration file's contents
    traffic: Dict[str, Any]        # the traffic file's contents
    driver: Any                    # the driver module
    end_to_end: List[Dict[str, Any]]
    per_layer: List[Tuple[Dict[str, Any], Any]]   # (entry, reader module)


def _reports(metric: Dict[str, Any], cell: str) -> bool:
    """Whether ``cell`` reports ``metric``: the cells its ``workloads``
    key lists, else every cell."""
    return cell in metric.get("workloads", [cell])


def resolve(cell_name: str, root: Path = REPO) -> Cell:
    bench = json.loads((root / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in bench["workloads"]}
    if cell_name not in cells:
        raise KeyError(f"no workload {cell_name!r} in BENCHMARK.json")
    entry = cells[cell_name]
    configs = {c["name"]: c for c in bench["configs"]}
    config = json.loads((root / configs[entry["config"]]["file"]).read_text())
    bdir = root / bench["paths"][0]
    traffic = json.loads((bdir / "traffic" / f"{entry['traffic']}.json")
                         .read_text())
    driver = load_module(bdir / "drivers" / f"{config['driver']}.py")
    e2e = [m for m in bench["end_to_end"] if _reports(m, cell_name)]
    per_layer = [(m, load_module(bdir / "metrics" / f"{m['name']}.py"))
                 for m in bench["per_layer"] if _reports(m, cell_name)]
    return Cell(cell_name, entry, config, traffic, driver, e2e, per_layer)


# ---------------------------------------------------------------------------
# What a driver hands back
# ---------------------------------------------------------------------------

@dataclass
class Check:
    name: str
    value: float
    limit: float
    higher_fails: bool = True      # value above limit fails

    @property
    def ok(self) -> bool:
        return self.value <= self.limit if self.higher_fails \
            else self.value >= self.limit


@dataclass
class Result:
    end_to_end: Dict[str, float]
    attempted: int
    failed: int
    checks: List[Check]
    records: Dict[str, Any] = field(default_factory=dict)  # for readers
    lines: List[str] = field(default_factory=list)         # earlier lines
    memory_peak_bytes: List[int] = field(default_factory=list)

    @property
    def correct(self) -> bool:
        return bool(self.checks) and all(c.ok for c in self.checks)


@dataclass
class Spec:
    cell: Cell
    seed: int
    seconds: float
    trace: bool
    devices: List[Any]
    t_start: float                 # process start on the perf_counter clock
    window: "Window"
    control: bool = False          # run the cell's control (bench/calibrate.py)


# ---------------------------------------------------------------------------
# The measured window: compile count and the optional trace
# ---------------------------------------------------------------------------

class Window:
    """Opens and closes the measured window.

    Counts every executable JAX obtains (compiled, or loaded from the
    persistent cache) while the window is open; that count should be 0.
    In a traced run, records a profiler trace of the first
    :data:`TRACE_SECONDS` of the window, with the Python function tracer
    off (only the benchmark's own host spans and the device's programs and
    ops are kept).  A traced run reports per-layer metrics only, all of
    them over that traced part of the window; stopping the trace (writing
    it out) blocks the host for a while after it.
    """

    def __init__(self, trace: bool, trace_dir: Path) -> None:
        self.trace = trace
        self.trace_dir = trace_dir
        self.compiled: List[str] = []      # programs obtained in the window
        self.is_open = False
        self.tracing = False
        self.t0 = self.t1 = self.trace_t0 = self.trace_t1 = 0.0
        self.trace_stop_s = 0.0
        import jax
        from jax._src import dispatch

        event = dispatch.BACKEND_COMPILE_EVENT

        def listen(name, _secs, **kw):
            if name == event and self.is_open:
                self.compiled.append(str(kw.get("fun_name", "?")))

        jax.monitoring.register_event_duration_secs_listener(listen)

    def open(self) -> float:
        if self.trace:
            import jax

            shutil.rmtree(self.trace_dir, ignore_errors=True)
            self.trace_dir.mkdir(parents=True)
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.host_tracer_level = 1
            jax.profiler.start_trace(str(self.trace_dir),
                                     profiler_options=opts)
            self.tracing = True
            self.trace_t0 = time.perf_counter()
        self.is_open = True
        self.t0 = time.perf_counter()
        return self.t0

    def poll(self, now: float) -> bool:
        """Stop the trace once it has covered its part of the window;
        True on the call that stopped it."""
        if self.tracing and now - self.trace_t0 >= TRACE_SECONDS:
            self._stop_trace(now)
            return True
        return False

    def close(self) -> float:
        self.t1 = time.perf_counter()
        self.is_open = False
        if self.tracing:
            self._stop_trace(self.t1)
        return self.t1

    def _stop_trace(self, now: float) -> None:
        import jax

        self.trace_t1 = now
        jax.profiler.stop_trace()
        self.tracing = False
        self.trace_stop_s = time.perf_counter() - now

    def measured(self) -> Tuple[float, float]:
        """The part of the window that per-layer metrics read: the traced
        part in a traced run, else the whole window."""
        return (self.trace_t0, self.trace_t1) if self.trace \
            else (self.t0, self.t1)

    def span(self, name: str):
        """A host span in the trace (a no-op context when not tracing)."""
        if self.trace:
            import jax

            return jax.profiler.TraceAnnotation(name)
        return contextlib.nullcontext()


# ---------------------------------------------------------------------------
# Set-up stages
# ---------------------------------------------------------------------------

STAMPS: List[Tuple[str, float]] = []


def stamp(stage: str) -> None:
    """Mark the end of a stage of set-up on the perf_counter clock."""
    STAMPS.append((stage, time.perf_counter()))


def setup_line(t_start: float) -> str:
    """Each stage's seconds, from process start: where set-up goes."""
    parts, prev = [], t_start
    for stage, t in STAMPS:
        parts.append(f"{stage}={t - prev!r}")
        prev = t
    return "setup: " + " ".join(parts)


# ---------------------------------------------------------------------------
# Device
# ---------------------------------------------------------------------------

def require_chips(n: int):
    """The first ``n`` accelerator devices; exits when there are fewer."""
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        sys.exit(f"bench: no TPU found (JAX's devices are "
                 f"{devs[0].platform}: {devs[0].device_kind}); no result")
    if len(devs) < n:
        sys.exit(f"bench: the cell needs {n} chips, found {len(devs)}")
    return devs[:n]


def memory_peaks(devices) -> List[int]:
    out = []
    for d in devices:
        stats = d.memory_stats() or {}
        out.append(int(stats.get("peak_bytes_in_use", 0)))
    return out


def enable_compile_cache() -> str:
    """The persistent compile cache: ``JAX_COMPILATION_CACHE_DIR`` when the
    machine sets it, else a fixed directory inside the checkout.  Every
    program is cached, however quickly it compiled."""
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(CACHE_DIR / "jax")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


# ---------------------------------------------------------------------------
# Per-layer metrics and the result line
# ---------------------------------------------------------------------------

@dataclass
class ReadCtx:
    """What a per-layer reader sees."""
    cell: Cell
    records: Dict[str, Any]
    trace: Optional[Dict[str, Any]]     # trace_reduce.reduce output
    peaks: Dict[str, float]
    devices: List[Any]


def read_per_layer(cell: Cell, ctx: ReadCtx) -> Dict[str, Dict[str, Any]]:
    out = {}
    for entry, reader in cell.per_layer:
        value = reader.read(ctx)
        if value is not None:
            out[entry["name"]] = {"value": float(value), "unit": entry["unit"]}
    return out


def check_summary(checks: List[Check]) -> Dict[str, Dict[str, float]]:
    return {c.name: {"value": c.value, "limit": c.limit} for c in checks}


def emit(result: Result, metrics: Dict[str, Dict[str, Any]],
         device: Dict[str, Any], breakdown: Optional[Dict] = None,
         out: Callable[[str], None] = print) -> Dict[str, Any]:
    """Print the earlier lines, the checks on stderr and the result line."""
    for line in result.lines:
        out(line)
    for c in result.checks:
        sys.stderr.write(f"check {c.name}: {c.value!r} limit {c.limit!r} "
                         f"{'ok' if c.ok else 'FAILED'}\n")
    sys.stderr.flush()
    line = {"correct": result.correct, "attempted": result.attempted,
            "failed": result.failed, "metrics": metrics, "device": device}
    if breakdown is not None:
        line["breakdown"] = breakdown
    line["checks"] = check_summary(result.checks)
    out(json.dumps(line))
    return line
