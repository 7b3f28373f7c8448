"""Model FLOPs of every decode and prefill in the traced window, per second
of it, as a percent of the chip's peak."""
from bench import readers


def read(ctx):
    return readers.mfu(ctx)
