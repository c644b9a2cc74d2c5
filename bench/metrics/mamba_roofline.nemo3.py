"""The Mamba-2 mixers' share of their roofline in the decode step: the
least time for the Mamba blocks' weights and every slot's recurrent state
read and written (``flops_hybrid.mamba_work``) over the device time a step
of the operations under the ``repro.mamba`` scope (``bench/hlo_scopes.py``),
in percent, over the traced steps."""
import flops_hybrid
import readers_mla_moe


def read(ctx):
    m = ctx.records["model"]
    return readers_mla_moe.scope_roofline(
        ctx, "repro.mamba", lambda s: flops_hybrid.mamba_work(m, len(s[3])))
