"""Model FLOPs of the tokens decoded in the window (``bench/flops.py``, each
token at its attended length) over one chip's bf16 peak times the window,
in percent."""
import readers


def read(ctx):
    t0, t1 = ctx.records["window"]
    steps = readers.steps_in(ctx, t0, t1)
    if not steps:
        return None
    return 100.0 * readers.decode_flops(ctx, steps) / (
        ctx.peaks["bf16_flops"] * (t1 - t0))
