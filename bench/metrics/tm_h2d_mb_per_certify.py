"""Megabytes (1e6 bytes) of host arrays a certification call uploads: the
mean ``h2d_bytes`` arg of the ``repro.ops.validate`` spans in the traced
window (a counter the program computes inside the span)."""
import program_spans


def read(ctx):
    red = program_spans.for_cell(ctx)
    calls = red and red["args"].get("repro.ops.validate")
    if not calls:
        return None
    return sum(c["h2d_bytes"] for c in calls) / len(calls) / 1e6
