"""Percent of the device's idle time in the traced window that no program
span names: the host held no ``repro.`` span, or only an outer loop's own
bookkeeping (the self time of ``repro.tm.events`` or ``repro.serve.step``)."""
import program_spans


def read(ctx):
    red = program_spans.for_cell(ctx)
    return None if red is None else program_spans.unattributed_share(red)
