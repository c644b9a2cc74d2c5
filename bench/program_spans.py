"""The program's own host spans in a traced run, on the device's clock.

The program opens ``repro.*`` host spans (``repro.obs.trace.host_span``) at
the layer boundaries of the Lilac-TM core and the serving step.  They are
profiler annotations, so a traced run (``bench/run.py --trace 1``) writes
them into the same ``.xplane.pb`` as the device planes, under
``harness.CACHE_DIR / "trace" / <cell>``.  :func:`for_cell` loads that file
once per process and reduces it (:func:`reduce`) to:

* ``self_s[name]``: seconds in which a span of that name was open with no
  span nested in it open (nesting is per host thread), clipped to the
  traced window;
* ``total_s[name]``, ``count[name]`` and ``args[name]`` (each span's args,
  such as ``h2d_bytes``, in time order);
* ``inside_s[(outer, inner)]``: seconds of ``inner`` spans nested at any
  depth in ``outer`` spans;
* ``idle_s[name]``: device-idle seconds in which ``name`` was the
  innermost open ``repro.`` span (``None``: none was open), the mean over
  the cell's chips, and ``idle_total_s`` their sum.  They are taken over
  the program's part of the window, from its first span's start to its
  last span's end (``program_s``): on a v5e host the profiler's own start
  and stop add about 0.28 s to every traced window, in which no code of
  the program runs.

The benchmark's own ``bench.*`` spans take part in the nesting, so a
probe's span inside a program span (``bench.certify_call`` in
``repro.tm.certify``) has self time of its own; they never count as the
innermost program span.

``python3 bench/program_spans.py <cell or trace dir>`` prints the table.
"""
from __future__ import annotations

import sys
from collections import defaultdict
from pathlib import Path
from typing import Dict, List, Optional, Sequence, Tuple

PROGRAM = "repro."
BENCH = "bench."
# the outer loops: their self time is the loop's own bookkeeping, which
# names no work
LOOPS = ("repro.tm.events", "repro.serve.step")

Span = Tuple[int, float, float, str, Dict]   # (line, start, end, name, args)

_CACHE: Dict[str, Optional[Dict]] = {}


def load(path: str) -> Tuple[List[Span], Dict[str, List[Tuple[float, float]]],
                             float]:
    """The ``repro.`` and ``bench.`` host spans (any thread), each device's
    busy intervals (its ``XLA Ops`` line, else ``XLA Modules``) and the
    window's length, all in nanoseconds from the trace's start."""
    from jax.profiler import ProfileData

    import trace_reduce

    data = ProfileData.from_file(path)
    spans: List[Span] = []
    busy: Dict[str, List[Tuple[float, float]]] = {}
    window_ns = 0.0
    n_line = 0
    for plane in data.planes:
        if plane.name == "Task Environment":
            st = dict(plane.stats)
            window_ns = float(st["profile_stop_time"]
                              - st["profile_start_time"])
            continue
        if trace_reduce.DEVICE_PLANE.match(plane.name):
            lines = {ln.name: ln for ln in plane.lines}
            ops = lines.get("XLA Ops") or lines.get("XLA Modules")
            if ops is not None:
                busy[plane.name] = [
                    (float(e.start_ns), float(e.start_ns + e.duration_ns))
                    for e in ops.events]
            continue
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            n_line += 1
            for ev in line.events:
                name = ev.name
                if name.startswith(PROGRAM) or name.startswith(BENCH):
                    spans.append((n_line, float(ev.start_ns),
                                  float(ev.start_ns + ev.duration_ns), name,
                                  dict(ev.stats) if name.startswith(PROGRAM)
                                  else {}))
    return spans, busy, window_ns


def reduce(spans: Sequence[Span], busy: Dict[str, Sequence[Tuple[float,
                                                                 float]]],
           window_ns: float) -> Dict:
    """Self time, nesting and device-idle attribution of ``spans``
    (see the module docstring); times in, seconds out."""
    import numpy as np

    import trace_reduce

    clipped = sorted(
        ((ln, max(s, 0.0), min(e, window_ns), name, args)
         for ln, s, e, name, args in spans if e > 0 and s < window_ns),
        key=lambda x: (x[0], x[1], -x[2]))
    self_s: Dict[str, float] = defaultdict(float)
    total_s: Dict[str, float] = defaultdict(float)
    count: Dict[str, int] = defaultdict(int)
    args: Dict[str, List[Dict]] = defaultdict(list)
    inside_s: Dict[Tuple[str, str], float] = defaultdict(float)
    # program-span timeline: (time, +1 open / -1 close, depth, name)
    edges: List[Tuple[float, int, int, str]] = []
    stack: List[list] = []                 # [end, name, child_s, dur]
    line = None

    def close(frame) -> None:
        self_s[frame[1]] += frame[3] - frame[2]

    for ln, s, e, name, a in clipped:
        if ln != line:
            while stack:
                close(stack.pop())
            line = ln
        while stack and stack[-1][0] <= s:
            close(stack.pop())
        dur = e - s
        if stack:
            stack[-1][2] += dur
        for frame in stack:
            inside_s[(frame[1], name)] += dur
        total_s[name] += dur
        count[name] += 1
        if a:
            args[name].append(a)
        depth = sum(1 for f in stack if f[1].startswith(PROGRAM))
        stack.append([e, name, 0.0, dur])
        if name.startswith(PROGRAM):
            edges.append((s, 1, depth, name))
            edges.append((e, -1, depth, name))
    while stack:
        close(stack.pop())

    # elementary intervals of the timeline, each with its innermost span
    edges.sort(key=lambda x: (x[0], x[1]))
    lo, hi = (edges[0][0], edges[-1][0]) if edges else (0.0, window_ns)
    bounds = [lo]
    labels: List[Optional[str]] = []
    active: List[Tuple[int, float, str]] = []
    for t, kind, depth, name in edges:
        if t > bounds[-1]:
            labels.append(max(active)[2] if active else None)
            bounds.append(t)
        if kind > 0:
            active.append((depth, t, name))
        else:
            for i in range(len(active) - 1, -1, -1):
                if active[i][0] == depth and active[i][2] == name:
                    del active[i]
                    break
    if hi > bounds[-1]:
        labels.append(None)
        bounds.append(hi)
    b = np.asarray(bounds)
    idle_s: Dict[Optional[str], float] = defaultdict(float)
    devices = sorted(busy) or [None]
    for dev in devices:
        union = np.asarray(trace_reduce._union(
            [(max(s, lo), min(e, hi)) for s, e in busy.get(dev, ())
             if e > lo and s < hi])).reshape(-1, 2)
        # busy time before each bound: piecewise linear in time
        lens = union[:, 1] - union[:, 0]
        after = np.cumsum(lens)
        busy_before = np.interp(
            b, union.ravel(), np.column_stack([after - lens, after]).ravel()
        ) if len(union) else np.zeros_like(b)
        idle = np.diff(b) - np.diff(busy_before)
        for label, sec in zip(labels, idle):
            idle_s[label] += float(sec) / 1e9 / len(devices)
    return {
        "window_s": window_ns / 1e9,
        "program_s": (hi - lo) / 1e9,
        "self_s": {k: v / 1e9 for k, v in self_s.items()},
        "total_s": {k: v / 1e9 for k, v in total_s.items()},
        "count": dict(count),
        "args": dict(args),
        "inside_s": {k: v / 1e9 for k, v in inside_s.items()},
        "idle_s": dict(idle_s),
        "idle_total_s": sum(idle_s.values()),
        "program": any(n.startswith(PROGRAM) for n in count),
    }


def for_trace_dir(trace_dir: Path, devices: Optional[Sequence[str]] = None
                  ) -> Optional[Dict]:
    """The reduction of the trace under ``trace_dir`` (cached), or None
    when it holds no trace or no program span."""
    import trace_reduce

    key = f"{trace_dir}|{devices}"
    if key not in _CACHE:
        try:
            path = trace_reduce.find_xplane(str(trace_dir))
        except FileNotFoundError:
            _CACHE[key] = None
            return None
        spans, busy, window_ns = load(path)
        if devices is not None:
            busy = {d: busy.get(d, []) for d in devices}
        red = reduce(spans, busy, window_ns)
        _CACHE[key] = red if red["program"] else None
    return _CACHE[key]


def for_cell(ctx) -> Optional[Dict]:
    """The reduction of a traced run's trace, for a per-layer reader."""
    import harness

    if not ctx.trace:
        return None
    devices = [f"/device:TPU:{d.id}" for d in ctx.devices]
    return for_trace_dir(harness.CACHE_DIR / "trace" / ctx.cell.name,
                         devices)


def self_ms(red: Dict, names: Sequence[str]) -> float:
    return 1e3 * sum(red["self_s"].get(n, 0.0) for n in names)


def unattributed_share(red: Dict) -> Optional[float]:
    """Percent of the device-idle time in which no program span, or only
    an outer loop's own bookkeeping, held the host."""
    if red["idle_total_s"] <= 0:
        return None
    loose = sum(red["idle_s"].get(n, 0.0) for n in (None,) + LOOPS)
    return 100.0 * loose / red["idle_total_s"]


def table(red: Dict) -> str:
    names = sorted(red["count"], key=lambda n: -red["self_s"].get(n, 0.0))
    rows = [f"{'span':<24} {'count':>7} {'total_s':>10} {'self_s':>10} "
            f"{'idle_s':>10}"]
    for n in names:
        rows.append(f"{n:<24} {red['count'][n]:>7} "
                    f"{red['total_s'][n]:>10.4f} {red['self_s'][n]:>10.4f} "
                    f"{red['idle_s'].get(n, 0.0):>10.4f}")
    rows.append(f"{'(no program span)':<24} {'':>7} {'':>10} {'':>10} "
                f"{red['idle_s'].get(None, 0.0):>10.4f}")
    rows.append(f"window_s {red['window_s']:.4f} program_s "
                f"{red['program_s']:.4f} idle_total_s "
                f"{red['idle_total_s']:.4f} unattributed_share "
                f"{unattributed_share(red)}")
    return "\n".join(rows)


if __name__ == "__main__":
    sys.path.insert(0, str(Path(__file__).resolve().parent))
    import harness

    arg = Path(sys.argv[1])
    where = arg if arg.exists() else harness.CACHE_DIR / "trace" / arg.name
    red = for_trace_dir(where)
    print(table(red) if red else f"no program spans under {where}")
