"""p95 of every gap between consecutive tokens of a request, both received
inside the window, in ms. In a backlog cell it is a per-layer reading:
about one step in twenty carries a prefill there, so the p95 falls on
plain or on prefill-carrying steps from run to run."""
from bench import e2e


def read(ctx):
    v = e2e.p95(e2e.itl_samples(ctx.logs, ctx.window))
    return None if v is None else v * 1e3
