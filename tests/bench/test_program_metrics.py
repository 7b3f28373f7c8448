"""The metrics the program counts itself: over a CPU rehearsal's window
its batch counters agree exactly with the harness's own step records, and
each reader of a program counter or span reads nothing without snapshots
or from a program that lacks the series, and the right value otherwise."""
import pytest

import rehearsal
from bench import report

SEED = 2**31 + 91
PHASE = "repro_engine_step_phase_seconds"


def test_batch_counters_equal_the_step_records(monkeypatch):
    raw = rehearsal.run(monkeypatch, SEED)
    ctx = report.Context(raw)
    steps = ctx.steps
    assert sum(s.n_decode for s in steps) > 0
    # nothing is preempted in the rehearsal, so every admitted prompt is
    # one whole-prompt prefill the records saw
    assert ctx.series("repro_decode_slots_total", "value") == sum(
        s.n_decode for s in steps)
    assert ctx.series("repro_decode_context_tokens_total", "value") == sum(
        s.context_tokens for s in steps)
    assert ctx.series("repro_prefill_tokens_total", "value") == sum(
        sum(s.prefill_lens) for s in steps)
    assert ctx.series(PHASE, "count", phase="dispatch") == sum(
        1 for s in steps if s.n_decode)
    for name in ("host_bound_ms", "step_gap_ms", "gc_pause_ms"):
        v = report.module("metrics", name).read(ctx)
        assert v is not None and v >= 0


def _hist(total, count, **labels):
    from repro.obs.metrics import label_str
    return {label_str(labels): {"sum": total, "count": count}}


def _snap(host_bound, gap_sum, gap_n, gc0, gc2, decode_steps, prefill_s,
          prefill_tokens):
    return {
        PHASE: {"series": {**_hist(1.0, decode_steps, phase="dispatch"),
                           **_hist(prefill_s, 7, phase="prefill")}},
        "repro_prefill_tokens_total": {"series": {"": prefill_tokens}},
        "repro_engine_host_bound_seconds_total": {"series": {"": host_bound}},
        "repro_engine_step_gap_seconds": {"series": _hist(gap_sum, gap_n)},
        "repro_gc_pause_seconds": {"series": {
            **_hist(gc0, 9, generation="0"), **_hist(gc2, 1,
                                                     generation="2")}},
    }


def _ctx(snaps):
    ctx = report.Context.__new__(report.Context)
    ctx.raw = {"snaps": snaps}
    return ctx


S0 = _snap(0.5, 0.2, 10, 0.01, 0.0, 100, 1.5, 24000)
S1 = _snap(0.9, 0.26, 30, 0.03, 0.05, 120, 2.1, 36000)
WANT = {"host_bound_ms": 0.4 / 20 * 1e3,     # per decode step
        "step_gap_ms": 0.06 / 20 * 1e3,      # per gap
        "gc_pause_ms": 0.07 / 20 * 1e3,      # all generations, per step
        "prefill_ms_per_ktok": 0.6 / 12 * 1e3}  # per 1000 prompt tokens


@pytest.mark.parametrize("name", sorted(WANT))
def test_program_metric_readers(name):
    read = report.module("metrics", name).read
    assert read(_ctx((None, None))) is None
    # a program without the series (the parent of the one that adds it)
    bare = {PHASE: S1[PHASE]}
    assert read(_ctx(({PHASE: S0[PHASE]}, bare))) is None
    assert read(_ctx((S0, S1))) == pytest.approx(WANT[name])
