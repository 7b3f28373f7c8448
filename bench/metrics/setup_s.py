"""Process start to the first timed request: weights, engine, warm-up of
every shape, compile-cache reads, the run-in before the window."""


def read(ctx):
    return ctx.setup_s
