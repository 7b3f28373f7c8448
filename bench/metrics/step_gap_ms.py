"""Mean wall time from the end of an engine step that left work pending to
the start of the next, over the traced window, in ms: the serve loop's
share of the step cycle, timed by the engine. A program without the
histogram reads nothing."""
HIST = "repro_engine_step_gap_seconds"


def read(ctx):
    s1 = ctx.raw["snaps"][1]
    if s1 is None or HIST not in s1:
        return None
    n = ctx.series(HIST, "count")
    if not n:
        return None
    return ctx.series(HIST, "sum") / n * 1e3
