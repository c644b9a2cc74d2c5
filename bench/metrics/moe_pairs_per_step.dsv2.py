"""Routed pairs the held experts served a decode step, summed over the MoE
layers: the program's ``moe_pairs`` counter (``RealBackend``, read back
with the argmax), the mean over the steps in the window."""
import readers_mla_moe


def read(ctx):
    steps = readers_mla_moe.steps_in(ctx, *ctx.records["window"])
    if not steps:
        return None
    return sum(s[4] for s in steps) / len(steps)
