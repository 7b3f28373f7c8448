"""Garbage-collector pauses per decode step of the traced window, summed
over the generations, in ms. A program without the histogram reads
nothing."""
HIST = "repro_gc_pause_seconds"
PHASE = "repro_engine_step_phase_seconds"


def _total(snap) -> float:
    return sum(s["sum"] for s in snap.get(HIST, {}).get("series", {}).values())


def read(ctx):
    s0, s1 = ctx.raw["snaps"]
    if s1 is None or HIST not in s1:
        return None
    n = ctx.series(PHASE, "count", phase="dispatch")
    if not n:
        return None
    return (_total(s1) - _total(s0)) / n * 1e3
