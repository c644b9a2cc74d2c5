"""``lease_validate``'s share of its roofline: the least time for what the
certification needs (``bench/flops.validate_work``: the packed entries, one
gathered version or lock per live entry, one verdict per row) over the
mean device time of the ``jit_lease_validate`` program in the trace, in
percent.  Bytes bound it."""
import flops
import readers


def read(ctx):
    device_s = readers.module_mean_s(ctx, "jit_lease_validate")
    t0, t1 = ctx.records["trace_window"]
    calls = [c for c in ctx.records["calls"]["validate"]
             if t0 <= c.t0 and c.t1 <= t1]
    if device_s is None or not calls:
        return None
    least = []
    for c in calls:
        items, _vers, _store_at, witems, _locks_at = c.args
        work = flops.validate_work(int((items >= 0).sum()),
                                   int((witems >= 0).sum()), items.shape[0],
                                   items.shape[1], witems.shape[1])
        least.append(flops.least_time(*work, ctx.peaks)[0])
    return 100.0 * (sum(least) / len(least)) / device_s
