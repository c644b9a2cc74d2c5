"""MLA's share of its roofline in the decode step: the least time for the
step's attention (``flops_mla_moe.mla_work``: MLA's weights read once, the
attended latents, the absorbed products) over the device time a step of
the operations under the ``repro.mla`` scope (``bench/hlo_scopes.py``), in
percent, over the traced steps."""
import flops_mla_moe
import readers_mla_moe


def read(ctx):
    m = ctx.records["model"]
    return readers_mla_moe.scope_roofline(
        ctx, "repro.mla", lambda s: flops_mla_moe.mla_work(m, s[3]))
