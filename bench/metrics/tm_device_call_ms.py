"""Mean host milliseconds of a ``validate_transactions`` or
``settle_lease_batch`` call in the window, until its result is back on the
host."""
import readers


def read(ctx):
    calls = ctx.records["calls"]["validate"] + ctx.records["calls"]["settle"]
    return readers.mean_ms([(c.t0, c.t1) for c in calls])
