"""Arithmetic the per-layer metric readers share (``bench/metrics``)."""
from __future__ import annotations

from typing import Optional

import flops


def idle_share(ctx) -> Optional[float]:
    """Percent of the traced window in which the cell's chips ran nothing,
    averaged over the chips."""
    tr = ctx.trace
    if not tr or not tr["busy_s"] or tr["window_s"] <= 0:
        return None
    busy = [tr["busy_s"].get(f"/device:TPU:{d.id}", 0.0)
            for d in ctx.devices]
    return 100.0 * (1.0 - sum(busy) / len(busy) / tr["window_s"])


def steps_in(ctx, t0: float, t1: float):
    return [s for s in ctx.records.get("steps", ())
            if t0 <= s[1] and s[2] <= t1]


def decode_flops(ctx, steps) -> float:
    m = ctx.records["model"]
    return sum(flops.decode_token_flops(m, n) for s in steps for n in s[3])


def mean_ms(spans) -> Optional[float]:
    """Mean host milliseconds of ``(.., t0, t1, ..)`` spans."""
    if not spans:
        return None
    return 1e3 * sum(t1 - t0 for t0, t1 in spans) / len(spans)


def module_mean_s(ctx, name: str) -> Optional[float]:
    tr = ctx.trace
    if not tr or not tr["module_n"].get(name):
        return None
    return tr["module_s"][name] / tr["module_n"][name]
