"""Mean host milliseconds of a ``RealBackend.step`` call of the MLA / MoE
model in the window, from the call to the ``argmax`` (and the expert
counters) back on the host."""
import readers


def read(ctx):
    t0, t1 = ctx.records["window"]
    return readers.mean_ms([(s[1], s[2]) for s in readers.steps_in(ctx, t0, t1)])
