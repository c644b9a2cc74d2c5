"""Host milliseconds a commit spends blocked on device results, over the
commits of the traced window: the program's ``repro.readback`` spans, and
the self time of the benchmark's probe spans around each call
(``bench.certify_call``, ``bench.settle_call``), which read the result back
on the program's behalf before handing it on."""
import program_spans

WAIT = ("repro.readback", "bench.certify_call", "bench.settle_call")


def read(ctx):
    red = program_spans.for_cell(ctx)
    if red is None or not ctx.records.get("commits"):
        return None
    return program_spans.self_ms(red, WAIT) / ctx.records["commits"]
