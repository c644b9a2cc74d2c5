"""The decode step program's share of its roofline: the least time the
chip needs for one step (``bench/flops_mla_moe.py``: weights read once, a
routed expert's only if it served a pair, the attended latents) over the
mean device time of the ``jit_step`` program in the trace, in percent,
over the traced steps."""
import flops
import flops_mla_moe
import readers
import readers_mla_moe


def read(ctx):
    device_s = readers.module_mean_s(ctx, "jit_step")
    steps = readers_mla_moe.steps_in(ctx, *ctx.records["trace_window"])
    if device_s is None or not steps:
        return None
    m = ctx.records["model"]
    least = [flops.least_time(*flops_mla_moe.decode_step_work(
        m, s[3], s[4], s[5]), ctx.peaks)[0] for s in steps]
    return 100.0 * (sum(least) / len(least)) / device_s
