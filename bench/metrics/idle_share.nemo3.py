"""Percent of the traced window in which no operation ran on the hybrid
Mamba-2 / MoE / GQA cell's chip (1 - busy / window from the trace)."""
import readers


def read(ctx):
    return readers.idle_share(ctx)
