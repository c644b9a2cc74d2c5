"""The routed experts' share of their roofline in the decode step: the
least time for the pairs the held experts served (``flops_mla_moe.
expert_work``: each expert with a pair read once, ``6 d d_expert`` FLOPs a
pair, by the step's ``moe_pairs`` and ``moe_experts`` counters) over the
device time a step of the operations under the ``repro.moe.experts``
scope (``bench/hlo_scopes.py``), in percent, over the traced steps."""
import flops_mla_moe
import readers_mla_moe


def read(ctx):
    m = ctx.records["model"]
    return readers_mla_moe.scope_roofline(
        ctx, "repro.moe.experts",
        lambda s: flops_mla_moe.expert_work(m, s[4], s[5]))
