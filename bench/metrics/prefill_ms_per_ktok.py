"""The engine's own prefill phase time over the traced window, per 1000
prompt tokens it admitted there, in ms. A program without the series reads
nothing."""
PHASE = "repro_engine_step_phase_seconds"
TOKENS = "repro_prefill_tokens_total"


def read(ctx):
    n = ctx.series(TOKENS, "value")
    if not n:
        return None
    return ctx.series(PHASE, "sum", phase="prefill") / n * 1e6
