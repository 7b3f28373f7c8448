"""Mean wall time from the end of one engine step to the start of the
next, over the gaps that began with work pending (serve loop), in ms."""
import numpy as np


def read(ctx):
    gaps = [b.t_start - a.t_end for a, b in zip(ctx.steps, ctx.steps[1:])
            if a.pending]
    return float(np.mean(gaps) * 1e3) if gaps else None
