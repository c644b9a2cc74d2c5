"""Commits per simulated second of the window, from the cluster's own
counters: a protocol change moves it, a faster implementation does not."""


def read(ctx):
    sim_s = ctx.records["sim_s"]
    return ctx.records["commits"] / sim_s if sim_s > 0 else None
