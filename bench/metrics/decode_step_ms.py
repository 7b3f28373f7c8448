"""The engine's own dispatch + host_sync + sample phases, summed over the
traced window's decode steps, per decode step, in ms."""
PHASE = "repro_engine_step_phase_seconds"


def read(ctx):
    n = ctx.series(PHASE, "count", phase="dispatch")
    if not n:
        return None
    total = sum(ctx.series(PHASE, "sum", phase=p)
                for p in ("dispatch", "host_sync", "sample"))
    return total / n * 1e3
