"""Host milliseconds of an engine step (``repro.serve.step``) in the traced
window less the time it waits on the device (``repro.readback`` nested in
it), the mean over steps."""
import program_spans


def read(ctx):
    red = program_spans.for_cell(ctx)
    n = red and red["count"].get("repro.serve.step")
    if not n:
        return None
    wait = red["inside_s"].get(("repro.serve.step", "repro.readback"), 0.0)
    return 1e3 * (red["total_s"]["repro.serve.step"] - wait) / n
