"""Host milliseconds a commit spends preparing device calls: the self time
of the certification drain (``repro.tm.certify``), the write-lock gather
(``repro.tm.write_locks``), the batch packing and version cast
(``repro.stm.pack``), the uploads and dispatches (``repro.ops.validate``,
``repro.ops.settle``) and the settle packing (``repro.tm.settle``), over
the commits of the traced window."""
import program_spans

PREP = ("repro.tm.certify", "repro.tm.write_locks", "repro.stm.pack",
        "repro.ops.validate", "repro.ops.settle", "repro.tm.settle")


def read(ctx):
    red = program_spans.for_cell(ctx)
    if red is None or not ctx.records.get("commits"):
        return None
    return program_spans.self_ms(red, PREP) / ctx.records["commits"]
