"""Run one cell of the benchmark once and print its result line.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell, its configuration, traffic mix, driver and per-layer metrics are
found by name from ``BENCHMARK.json`` (see ``bench/harness.py``).  The run
loads and warms up (``setup_s``), measures for ``--seconds``, checks what
the timed path produced against the plain reference, and prints as its
last line one JSON object: ``correct``, ``attempted``, ``failed``,
``metrics`` (the cell's end-to-end metrics, or with ``--trace 1`` its
per-layer ones), ``device`` and, traced, ``breakdown``; ``checks`` holds
each compared number beside its limit.  Without a TPU, or with fewer chips
than the cell asks for, it exits non-zero and prints no result.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

sys.path.insert(0, str(Path(__file__).resolve().parent))
sys.path.insert(0, str(Path(__file__).resolve().parents[1] / "src"))

import harness  # noqa: E402


def execute(cell: "harness.Cell", devices, seed: int, seconds: float,
            trace: bool, t_start: float) -> dict:
    """Everything after the look for chips: set-up, window, check, line."""
    cache = harness.enable_compile_cache()
    dev = devices[0]
    print(f"device: platform={dev.platform} kind={dev.device_kind} "
          f"count={len(devices)} compile_cache={cache}")
    window = harness.Window(trace, harness.CACHE_DIR / "trace" / cell.name)
    spec = harness.Spec(cell=cell, seed=seed, seconds=seconds, trace=trace,
                        devices=devices, t_start=t_start, window=window)
    result = cell.driver.run(spec)
    gc.collect()

    import flops
    import trace_reduce

    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices),
              "memory_peak_bytes": max(result.memory_peak_bytes)}
    breakdown = None
    if trace:
        t_load = time.perf_counter()
        events, window_ns = trace_reduce.load(
            trace_reduce.find_xplane(str(window.trace_dir)))
        t_reduce = time.perf_counter()
        red = trace_reduce.reduce(events, window_ns)
        result.lines.append(
            f"trace: stop_s={window.trace_stop_s!r} "
            f"load_s={t_reduce - t_load!r} "
            f"reduce_s={time.perf_counter() - t_reduce!r} "
            f"events={len(events)}")
        busy = [red["busy_s"].get(f"/device:TPU:{d.id}", 0.0)
                for d in devices]
        device["busy_s"] = sum(busy) / len(busy)
        device["window_s"] = red["window_s"]
        result.lines.append("trace: busy_s per chip " + " ".join(
            f"{b!r}" for b in busy) + f" window_s {red['window_s']!r}")
        ctx = harness.ReadCtx(cell=cell, records=result.records, trace=red,
                              peaks=flops.peaks(dev.device_kind),
                              devices=devices)
        metrics = harness.read_per_layer(cell, ctx)
        breakdown = trace_reduce.breakdown(red)
    else:
        metrics = {m["name"]: {"value": result.end_to_end[m["name"]],
                               "unit": m["unit"]}
                   for m in cell.end_to_end}
    return harness.emit(result, metrics, device, breakdown)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    cell = harness.resolve(args.workload)
    harness.stamp("start_and_resolve")
    devices = harness.require_chips(int(cell.entry["chips"]))
    harness.stamp("jax_and_runtime")
    execute(cell, devices, args.seed, args.seconds, bool(args.trace), T_START)
    return 0


if __name__ == "__main__":
    sys.exit(main())
