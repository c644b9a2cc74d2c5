"""Arithmetic the MLA / MoE cell's per-layer readers share
(``bench/metrics/*.dsv2.py``)."""
from __future__ import annotations

from typing import Callable, Optional

import flops
import hlo_scopes


def steps_in(ctx, t0: float, t1: float):
    """The driver's ``moe_steps`` inside ``[t0, t1]``: ``(pod, t0, t1,
    lengths, pairs, experts)``, ``pairs`` and ``experts`` the step's
    ``moe_pairs`` and ``moe_experts`` counters."""
    return [s for s in ctx.records.get("moe_steps", ())
            if t0 <= s[1] and s[2] <= t1]


def scope_roofline(ctx, scope: str,
                   work: Callable[[tuple], tuple]) -> Optional[float]:
    """Percent of ``scope``'s roofline in ``jit_step``: the mean least time
    of ``work(step)`` (``(flops, bytes)``) over the traced steps, over the
    device seconds a run of the scope's operations."""
    runs = (ctx.trace or {}).get("module_n", {}).get("jit_step")
    sec = hlo_scopes.scope_seconds(ctx.trace, "jit_step",
                                   ctx.records.get("op_scopes", {}))
    steps = steps_in(ctx, *ctx.records["trace_window"])
    if not runs or not sec.get(scope) or not steps:
        return None
    least = [flops.least_time(*work(s), ctx.peaks)[0] for s in steps]
    return 100.0 * (sum(least) / len(least)) / (sec[scope] / runs)
