"""The arithmetic that readers of several cells share (``bench/readers.py``):
the ``.batch`` readers give, on one synthetic window, the values that
their own arithmetic gave before it moved there, and each ``.poisson``
reader gives its ``.batch`` twin's value; a rehearsed Poisson cell reads
its own per-layer metrics."""
import json

import pytest

import rehearsal
from bench import harness, report, spec
from bench.spec import BENCH

OPT13 = json.loads((BENCH / "configs" / "opt-1.3b.json").read_text())
# four steps of a window: decoding slots' contexts and prompts prefilled
STEPS = [([320] * 15 + [323], [135, 512]), ([25, 26, 26], []),
         ([1], [1920]), ([], [768])]
# the readers' values on that window before the arithmetic moved
BEFORE = {"mfu.batch": 5.79332132177868,
          "idle_share.batch": 20.000000000000007,
          "batch_occupancy.batch": 41.666666666666664}
POISSON_CELL = "opt-1.3b.long-prompt-poisson"
SEED = 2**31 + 171


def synthetic():
    ctx = report.Context.__new__(report.Context)
    ctx.mc, ctx.cell = OPT13, {"n_slots": 16}
    ctx.steps = [harness.StepRecord(0, 0, c, p, True, True)
                 for c, p in STEPS]
    ctx.span = (10.0, 10.75)
    ctx.trace = {"busy_s": 0.6, "window_s": 0.75}
    ctx.raw = {"device": {"kind": "TPU v5 lite"}}
    return ctx


@pytest.mark.parametrize("name", sorted(BEFORE))
def test_moved_arithmetic_is_the_parents(name):
    assert report.module("metrics", name).read(synthetic()) == BEFORE[name]


@pytest.mark.parametrize("name", sorted(BEFORE))
def test_poisson_twin_reads_the_same(name):
    twin = name.replace(".batch", ".poisson")
    ctx = synthetic()
    assert report.module("metrics", twin).read(ctx) == (
        report.module("metrics", name).read(ctx))


def test_poisson_cell_reads_its_layer_metrics(monkeypatch):
    per_layer = spec.load(POISSON_CELL).per_layer
    raw = rehearsal.run(monkeypatch, SEED, mix=rehearsal.POISSON,
                        trace=True, per_layer=per_layer)
    # the peaks table knows the chip, not the CPU: read the arithmetic
    # against the chip's peaks (a CPU run measures no device time)
    raw["device"]["kind"] = "TPU v5 lite"
    line = report.result(raw, trace=True)
    assert line["correct"] is True
    got = line["metrics"]
    for name in ("mfu.poisson", "batch_occupancy.poisson",
                 "prefill_ms_per_ktok", "loop_gap_ms", "step_gap_ms",
                 "decode_step_ms", "host_bound_ms", "gc_pause_ms"):
        assert got[name]["value"] >= 0, name
    assert 0 < got["batch_occupancy.poisson"]["value"] <= 100
    assert got["prefill_ms_per_ktok"]["value"] > 0
    # the CPU backend has no device plane: what the device trace gives is
    # left out of the line, and read once a trace is there
    assert raw["trace"] is None
    assert not {"idle_share.poisson", "fused_sparse_ffn_roofline",
                "paged_window_attention_roofline"} & set(got)
    ctx = report.Context(dict(raw, trace={"busy_s": 0.3, "window_s": 0.4}))
    assert report.module("metrics", "idle_share.poisson").read(
        ctx) == pytest.approx(25.0)
