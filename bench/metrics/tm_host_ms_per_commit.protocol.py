"""Host milliseconds a commit spends in the Python protocol: the self time
of the event loop (``repro.tm.events``: heap, dispatch and every handler
no nested span names), transaction bodies (``repro.tm.execute``), group
communication deliveries (``repro.tm.deliver``) and commit application
(``repro.tm.apply``), over the commits of the traced window."""
import program_spans

PROTOCOL = ("repro.tm.events", "repro.tm.execute", "repro.tm.deliver",
            "repro.tm.apply")


def read(ctx):
    red = program_spans.for_cell(ctx)
    if red is None or not ctx.records.get("commits"):
        return None
    return program_spans.self_ms(red, PROTOCOL) / ctx.records["commits"]
