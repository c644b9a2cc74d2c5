"""Model FLOPs of the steps decoded in the window (``bench/flops_hybrid.py``:
each token at its attended length with its Mamba recurrence, each routed
pair's relu² expert, by the step's ``moe_pairs`` counter) over one chip's
bf16 peak times the window, in percent."""
import flops_hybrid
import readers_mla_moe


def read(ctx):
    t0, t1 = ctx.records["window"]
    steps = readers_mla_moe.steps_in(ctx, t0, t1)
    if not steps:
        return None
    m = ctx.records["model"]
    work = sum(flops_hybrid.decode_flops(m, s[3], s[4]) for s in steps)
    return 100.0 * work / (ctx.peaks["bf16_flops"] * (t1 - t0))
