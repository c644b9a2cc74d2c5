"""Readings that the limits of ``correct`` are set from (not run by the
benchmark's own runs).

    python3 bench/calibrate.py --workload <cell> --seeds 11,12,13 \\
        --seconds 5 [--control]

Runs the cell's driver once per seed in this one process (a short window at
the cell's own load and sizes) and prints, per seed, ``correct`` and every
number the cell compares beside its limit.  With ``--control`` the driver
runs the cell's control in the program's place, and the same checks judge
it (``correct`` should come out false):

* serving: the plain reference computed with float8 (e4m3) operands in
  every matmul, the precision below the configuration's bfloat16, over the
  same prompts and tokens the program served; ``max_logit_gap`` then reads
  the reference gap of the token the float8 forward puts first;
* Lilac-TM: certification that skips the read-version check (each read is
  compared with the store's own version), which breaks the serializability
  the configuration states.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent))
sys.path.insert(0, str(Path(__file__).resolve().parents[1] / "src"))

import harness  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=5.0)
    ap.add_argument("--control", action="store_true")
    args = ap.parse_args(argv)

    cell = harness.resolve(args.workload)
    devices = harness.require_chips(int(cell.entry["chips"]))
    harness.enable_compile_cache()
    for seed in (int(s) for s in args.seeds.split(",")):
        win = harness.Window(False, harness.CACHE_DIR / "calibrate")
        spec = harness.Spec(cell=cell, seed=seed, seconds=args.seconds,
                            trace=False, devices=devices,
                            t_start=time.perf_counter(), window=win,
                            control=args.control)
        res = cell.driver.run(spec)
        print(json.dumps({"seed": seed, "control": args.control,
                          "correct": res.correct,
                          "checks": harness.check_summary(res.checks),
                          "lines": [ln for ln in res.lines
                                    if ln.startswith(("check:", "window:"))]}),
              flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
