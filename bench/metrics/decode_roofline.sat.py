"""The decode step program's share of its roofline: the least time the
chip needs for one step (``bench/flops.py``: weights read once, the
attended part of each session's cache) over the mean device time of the
``jit_step`` program in the trace, in percent, over the traced steps."""
import flops
import readers


def read(ctx):
    device_s = readers.module_mean_s(ctx, "jit_step")
    steps = readers.steps_in(ctx, *ctx.records["trace_window"])
    if device_s is None or not steps:
        return None
    m = ctx.records["model"]
    least = [flops.least_time(*flops.decode_step_work(m, s[3]), ctx.peaks)[0]
             for s in steps]
    return 100.0 * (sum(least) / len(least)) / device_s
