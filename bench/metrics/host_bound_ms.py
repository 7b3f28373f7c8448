"""Time in which the device had nothing queued, per decode step of the
traced window, in ms: the engine's own count from each blocking fetch of a
device result to the next launch, while work was pending. A program
without the counter reads nothing."""
COUNTER = "repro_engine_host_bound_seconds_total"
PHASE = "repro_engine_step_phase_seconds"


def read(ctx):
    s1 = ctx.raw["snaps"][1]
    if s1 is None or COUNTER not in s1:
        return None
    n = ctx.series(PHASE, "count", phase="dispatch")
    if not n:
        return None
    return ctx.series(COUNTER, "value") / n * 1e3
