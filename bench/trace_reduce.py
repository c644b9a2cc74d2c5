"""Reduce a ``jax.profiler`` trace of the measured window to metrics.

``load`` reads the ``.xplane.pb`` the profiler wrote into a flat list of
events ``(plane, line, name, start_ns, dur_ns)`` and the traced window's
length; ``reduce`` turns that list into:

* ``busy_s[device]``: the union of the intervals in which an operation ran
  on that device (its ``XLA Ops`` line, else its ``XLA Modules`` line),
  clipped to the window;
* ``module_s[name]``: device-busy seconds per compiled program (the union
  of the op intervals inside each of its runs), by the program's name
  without its ``(id)`` suffix (``jit_step``, ``jit_lease_validate``),
  summed over every device, with ``module_n`` the number of runs (a
  run's own span also holds the time a launched program waited, so it is
  not the program's device time);
* ``op_s[name]``: device seconds per operation, named ``program/op``
  (the op's HLO name, inside the program whose run encloses it), for the
  breakdown;
* ``gaps``: the device's idle gaps, each labelled with the host span the
  benchmark had open over most of it.

Timestamps are nanoseconds from the start of the trace on every plane.
"""
from __future__ import annotations

import glob
import os
import re
from collections import defaultdict
from typing import Dict, List, Sequence, Tuple

Event = Tuple[str, str, str, float, float]

DEVICE_PLANE = re.compile(r"^/device:(TPU|GPU):(\d+)$")
_SUFFIX = re.compile(r"\(\d+\)$")


def find_xplane(trace_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def load(path: str, host_prefix: str = "bench.") -> Tuple[List[Event], float]:
    """Events of every device plane, the host spans whose names start with
    ``host_prefix`` (any thread), and the window's length in nanoseconds."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    events: List[Event] = []
    window_ns = 0.0
    for plane in data.planes:
        if plane.name == "Task Environment":
            stats = dict(plane.stats)
            window_ns = float(stats["profile_stop_time"]
                              - stats["profile_start_time"])
            continue
        device = DEVICE_PLANE.match(plane.name) is not None
        if not device and not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if device or ev.name.startswith(host_prefix):
                    events.append((plane.name, line.name, ev.name,
                                   float(ev.start_ns), float(ev.duration_ns)))
    return events, window_ns


def _union(intervals: Sequence[Tuple[float, float]]) -> List[Tuple[float, float]]:
    out: List[List[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def module_name(name: str) -> str:
    return _SUFFIX.sub("", name)


def op_name(name: str) -> str:
    """``%fusion.3 = bf16[..] fusion(..), ..`` -> ``%fusion.3``."""
    return name.split(" = ", 1)[0]


def _enclosing(modules: List[Tuple[float, float, str]], t: float) -> str:
    lo, hi = 0, len(modules)
    while lo < hi:                      # last module starting at or before t
        mid = (lo + hi) // 2
        if modules[mid][0] <= t:
            lo = mid + 1
        else:
            hi = mid
    if lo and modules[lo - 1][1] >= t:
        return module_name(modules[lo - 1][2])
    return "?"


def reduce(events: Sequence[Event], window_ns: float,
           host_prefix: str = "bench.") -> Dict:
    """Busy time, per-program and per-op device time, and idle gaps."""
    by_line: Dict[Tuple[str, str], List[Tuple[float, float, str]]] = \
        defaultdict(list)
    host: List[Tuple[float, float, str]] = []
    for plane, line, name, start, dur in events:
        if DEVICE_PLANE.match(plane):
            by_line[(plane, line)].append((start, start + dur, name))
        elif name.startswith(host_prefix):
            host.append((start, start + dur, name))
    devices = sorted({p for p, _ in by_line},
                     key=lambda p: int(DEVICE_PLANE.match(p).group(2)))
    busy_s: Dict[str, float] = {}
    module_s: Dict[str, float] = defaultdict(float)
    module_n: Dict[str, int] = defaultdict(int)
    op_s: Dict[str, float] = defaultdict(float)
    gaps: List[Tuple[str, float]] = []
    for dev in devices:
        ops = by_line.get((dev, "XLA Ops")) or by_line.get(
            (dev, "XLA Modules"), [])
        spans = _union([(max(s, 0.0), min(e, window_ns))
                        for s, e, _ in ops if e > 0 and s < window_ns])
        busy_s[dev] = sum(e - s for s, e in spans) / 1e9
        modules = sorted(by_line.get((dev, "XLA Modules"), []))
        for s, e, name in modules:
            module_n[module_name(name)] += 1
        inside: Dict[str, List[Tuple[float, float]]] = defaultdict(list)
        for s, e, name in by_line.get((dev, "XLA Ops"), []):
            prog = _enclosing(modules, s)
            op_s[f"{prog}/{op_name(name)}"] += (e - s) / 1e9
            inside[prog].append((s, e))
        for prog, spans_in in inside.items():
            module_s[prog] += sum(e - s for s, e in _union(spans_in)) / 1e9
        edges = [0.0] + [x for span in spans for x in span] + [window_ns]
        gaps += _label_gaps(host, [(g0, g1) for g0, g1 in
                                   zip(edges[::2], edges[1::2]) if g1 > g0])
    return {
        "devices": devices,
        "window_s": window_ns / 1e9,
        "busy_s": busy_s,
        "module_s": dict(module_s),
        "module_n": dict(module_n),
        "op_s": dict(op_s),
        "gaps": sorted(gaps, key=lambda g: -g[1]),
    }


def _label_gaps(host, gaps):
    """``(label, seconds)`` per gap: the host span that covers most of it;
    of spans that cover it equally, the one opened last (the innermost)."""
    host = sorted(host)
    out, active, i = [], [], 0
    for g0, g1 in gaps:                          # gaps come in time order
        while i < len(host) and host[i][0] < g1:
            active.append(host[i])
            i += 1
        active = [h for h in active if h[1] > g0]
        best, key = "host (no span)", (0.0, float("-inf"))
        for s, e, name in active:
            cover = min(e, g1) - max(s, g0)
            if cover > 0 and (cover, s) > key:
                best, key = name, (cover, s)
        out.append((best, (g1 - g0) / 1e9))
    return out


def breakdown(red: Dict, top: int = 10) -> Dict:
    """The contract's ``breakdown``: top device ops and longest idle gaps."""
    ops = sorted(red["op_s"].items(), key=lambda kv: -kv[1])[:top]
    gaps: Dict[str, float] = defaultdict(float)
    for label, sec in red["gaps"]:
        gaps[label] += sec
    return {
        "device_ops": [[n, s] for n, s in ops],
        "idle_gaps": [[n, s] for n, s in
                      sorted(gaps.items(), key=lambda kv: -kv[1])[:top]],
    }
