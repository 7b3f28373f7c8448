"""All output tokens clients received inside the window, per second of it."""
from bench import e2e


def read(ctx):
    return e2e.output_tokens_per_s(ctx.logs, ctx.window)
