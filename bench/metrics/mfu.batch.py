"""Model FLOPs of every decode and prefill in the traced window, per second
of it, as a percent of the chip's peak."""
from bench import readers


def read(ctx):
    t0, t1 = ctx.span
    if t0 is None or t1 is None or not ctx.steps:
        return None
    rate = readers.window_flops(ctx) / (t1 - t0)
    return 100.0 * rate / ctx.peak["flops_per_s"]
