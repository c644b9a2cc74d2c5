"""Percent of the traced window in which no operation ran on the cell's
chips (1 - busy / window from the trace), the mean over its chips."""
import readers


def read(ctx):
    return readers.idle_share(ctx)
