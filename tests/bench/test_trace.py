"""The trace-to-metrics reduction on a small synthesised trace: kernel
time by name, the union of busy intervals, idle share and its gaps, and
the roofline arithmetic with its bound named."""
import types

import pytest

from bench import harness, report, trace
from bench.peaks import peaks

US = 1_000_000  # picoseconds per microsecond


def _events(evs):
    out = []
    for name_id, start_us, dur_us, stats in evs:
        st = "".join(f" stats {{ metadata_id: {k} {v} }}" for k, v in stats)
        out.append(f"events {{ metadata_id: {name_id} offset_ps: "
                   f"{start_us * US} duration_ps: {dur_us * US}{st} }}")
    return "\n".join(out)


NAMES = {
    1: "%fused_sparse_ffn.9 = (f32[16,1,2048]{2,1,0:T(1,128)}, "
       "f32[16,64,1,128]) custom-call(bf16[16,1,2048] %x)",
    2: "%paged_window_attention.3 = f32[16,32,1,64]{3,2,1,0} "
       "custom-call(s32[16,16] %t)",
    3: "%fusion.1 = bf16[16,32,64]{2,0,1} "
       "fusion(f32[16,32,1,64] %paged_window_attention.3)",
    4: "%copy.2 = bf16[24,257,32,64,64]{1,4,3,2,0} "
       "copy(bf16[24,257,32,64,64] %p)",
    5: "jit_decode",
    7: "%while.6 = (s32[], bf16[16,2048]) "
       "while((s32[], bf16[16,2048]) %tuple.85)",
}


def _meta(names):
    return "\n".join(f'event_metadata {{ key: {k} value {{ id: {k} name: '
                     f'"{v}" }} }}' for k, v in names.items())


def xspace(ops, steps):
    """ops: (name id, start us, duration us, stats); steps: (start us,
    duration us, step_num). Event names: 1 fused_sparse_ffn, 2
    paged_window_attention, 3 a fusion that reads the attention kernel's
    output, 4 copy.2, 5 the layer loop that spans them all, as TPU traces
    name them: by HLO instruction text."""
    return f"""
planes {{ id: 1 name: "/device:TPU:0"
  lines {{ id: 1 name: "XLA Ops" timestamp_ns: 0
{_events(ops)} }}
  lines {{ id: 2 name: "XLA Modules" timestamp_ns: 0
{_events([(5, 0, 1000, [])])} }}
{_meta(NAMES)}
  stat_metadata {{ key: 9 value {{ id: 9 name: "tf_op" }} }}
}}
planes {{ id: 2 name: "/host:CPU"
  lines {{ id: 3 name: "bench-executor" timestamp_ns: 0
{_events([(6, s, d, [(8, f"int64_value: {n}")]) for s, d, n in steps])} }}
  event_metadata {{ key: 6 value {{ id: 6 name: "engine_step" }} }}
  stat_metadata {{ key: 8 value {{ id: 8 name: "step_num" }} }}
}}"""


PAGED = [(9, 'str_value: "jit(decode)/paged_window_attention"')]
LOOP = (7, 0, 900, [])  # the layer scan: spans every op of both steps
# two steps: [0, 400) us and [500, 900) us; 100 us between them
STEPS = [(0, 400, 0), (500, 400, 1)]
OPS = [
    LOOP,
    (1, 10, 100, []), (2, 120, 30, PAGED), (3, 140, 60, PAGED),
    (3, 180, 40, []),                       # overlaps the one before
    (1, 510, 100, []), (2, 620, 30, PAGED), (4, 700, 150, []),
    (3, 950, 20, []),                       # after the window: left out
]


def opt_mc(layers):
    return {"model_type": "opt", "num_hidden_layers": layers,
            "hidden_size": 2048, "ffn_dim": 8192, "num_attention_heads": 32,
            "vocab_size": 50272, "init_std": 0.02}


def rec(prefill):
    return harness.StepRecord(0, 0, [100] * 4, [64] if prefill else [],
                              True, True)


@pytest.fixture
def reduced():
    from jax.profiler import ProfileData
    pd = ProfileData.from_text_proto(xspace(OPS, STEPS))
    return trace.reduce(pd, [rec(True), rec(False)])


def test_kernel_time_by_name(reduced):
    assert reduced["kernels"]["fused_sparse_ffn"] == (2, pytest.approx(200e-6))
    assert reduced["kernels"]["paged_window_attention"] == (
        2, pytest.approx(60e-6))


def test_busy_is_the_union_of_op_intervals(reduced):
    # 10-110, 120-150, 140-220 (two overlapping fusions), 510-610,
    # 620-650, 700-850: 100 + 100 + 100 + 30 + 150 us
    assert reduced["window_s"] == pytest.approx(900e-6)
    assert reduced["busy_s"] == pytest.approx(480e-6)
    ctx = types.SimpleNamespace(trace=reduced)
    idle = report.module("metrics", "idle_share.batch").read(ctx)
    assert idle == pytest.approx(100 * (1 - 480 / 900))


def test_idle_gaps_are_labelled_by_host_state(reduced):
    gaps = dict((round(t * 1e6), name) for name, t in reduced["idle_gaps"])
    # the gap 220-510 us is cut where the host changed state
    assert gaps[180] == "engine.step (prefill + decode)"  # 220-400
    assert gaps[100] == "between engine steps"             # 400-500
    assert gaps[50] == "engine.step (decode)"              # 650-700
    assert sum(reduced["idle_by_host_state"].values()) == pytest.approx(
        420e-6)


def test_top_ops_name_the_kernels(reduced):
    top = dict(reduced["top_ops"])
    assert top["fused_sparse_ffn"] == pytest.approx(200e-6)
    assert top["fusion.1 bf16[16,32,64]"] == pytest.approx(100e-6)
    assert top["copy.2 bf16[24,257,32,64,64]"] == pytest.approx(150e-6)
    assert not any(k.startswith(("while", "jit_")) for k in top)


def test_roofline_from_trace_and_counts(reduced, capsys):
    ctx = report.Context.__new__(report.Context)
    ctx.trace, ctx.mc = reduced, opt_mc(1)
    ctx.steps = [rec(True), rec(False)]
    ctx.raw = {"device": {"kind": "TPU v5 lite"}}
    share = report.module("metrics", "fused_sparse_ffn_roofline").read(ctx)
    need = 2 * ((64 * 2 * 128 * 2048 * 2 + 2 * 4 * 2048 * 2)
                / peaks("TPU v5 lite")["hbm_bytes_per_s"])
    assert share == pytest.approx(100 * need / 200e-6)
    assert "bound by memory" in capsys.readouterr().err


def test_roofline_refuses_a_call_count_it_cannot_explain(reduced):
    ctx = report.Context.__new__(report.Context)
    ctx.trace = reduced
    ctx.mc = opt_mc(2)
    ctx.steps = [rec(True), rec(False)]
    ctx.raw = {"device": {"kind": "TPU v5 lite"}}
    assert report.module("metrics", "fused_sparse_ffn_roofline").read(
        ctx) is None
