"""Mean share of the slots that decode, over the decode steps of the window."""


def read(ctx):
    steps = [s for s in ctx.steps if s.n_decode]
    if not steps:
        return None
    n = ctx.cell["n_slots"]
    return 100.0 * sum(s.n_decode for s in steps) / (n * len(steps))
