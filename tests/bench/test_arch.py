"""The architecture modules (``bench/arch/<model_type>.py``).

OPT's module reproduces, exactly, the shapes, counts and flops the harness
computed before architectures had modules of their own (the literals
below were recorded from that code), and its reference gives the same
logits. A second architecture, a test-only module under ``tests/bench``
(GQA, rotary, RMSNorm, a gated ReLU FFN, an untied head), runs through
``harness.run_cell`` by its own files alone. A file that names no
architecture, or one that the program's registry contradicts, is refused.
"""
import hashlib
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import arch_gqa_glu
import rehearsal
from bench import arch, flops, harness, readers, report, weights
from bench.spec import BENCH

HERE = BENCH.parent / "tests" / "bench"
CONFIGS = {n: json.loads((BENCH / "configs" / f"{n}.json").read_text())
           for n in ("opt-1.3b", "opt-6.7b-16l")}
GQA = json.loads((HERE / "gqa_glu.json").read_text())
# the GQA cell's own limit, set on the CPU from 14 seeds of the program
# (mean gap 0 to 5.75e-5: its bf16 RoPE and gate flip near-tied argmaxes
# more often than OPT's block) and of the float8 control (8.9e-4 to 2.6e-3)
GQA_CELL = dict(rehearsal.CELL, mean_logit_gap_limit=2.5e-4)
# 12 query heads over 4 KV heads, 16 wide: the program pads each KV head's
# group of 3 query heads to 4 slots, so slot 3 of every group holds no head.
# GQA_CELL's limit holds for it: on the CPU, 12 seeds of the program read a
# mean gap of 3.1e-6 to 6.5e-5, of the float8 control 1.3e-3 to 3.2e-3
PADDED = dict(GQA, num_attention_heads=12, head_dim=16)
SEED = 2**31 + 303

OPT13 = {
  "shapes": [
    ("embed", (51200, 2048), None),
    ("pos_embed", (2048, 2048), None),
    ("final_norm/scale", (2048,), 1.0),
    ("final_norm/bias", (2048,), 0.0),
    ("layers/ln1/scale", (24, 2048), 1.0),
    ("layers/ln1/bias", (24, 2048), 0.0),
    ("layers/ln2/scale", (24, 2048), 1.0),
    ("layers/ln2/bias", (24, 2048), 0.0),
    ("layers/attn/wq", (24, 2048, 32, 64), None),
    ("layers/attn/wk", (24, 2048, 32, 64), None),
    ("layers/attn/wv", (24, 2048, 32, 64), None),
    ("layers/attn/wo", (24, 32, 64, 2048), None),
    ("layers/ffn/wu", (24, 2048, 8192), None),
    ("layers/ffn/wd", (24, 8192, 2048), None),
  ],
  "weight_macs": 1207959552,
  "attn_macs": [98304.0, 29491200.0, 884785152.0],
  "decode_flops": [2641494016.0, 42956554240.0],
  "prefill_flops": [2622029824.0, 328159854592.0, 1262976630784.0],
  "fused_sparse_ffn": [
      (1073741824.0, 67239936.0),
      (201326592.0, 67133440.0),
      (67108864.0, 67117056.0),
  ],
  "paged_window_attention": [
      (41967616.0, 42098688.0),
      (630784.0, 655360.0),
      (8192.0, 16384.0),
  ],
}

OPT67 = {
  "shapes": [
    ("embed", (51200, 4096), None),
    ("pos_embed", (2048, 4096), None),
    ("final_norm/scale", (4096,), 1.0),
    ("final_norm/bias", (4096,), 0.0),
    ("layers/ln1/scale", (16, 4096), 1.0),
    ("layers/ln1/bias", (16, 4096), 0.0),
    ("layers/ln2/scale", (16, 4096), 1.0),
    ("layers/ln2/bias", (16, 4096), 0.0),
    ("layers/attn/wq", (16, 4096, 32, 128), None),
    ("layers/attn/wk", (16, 4096, 32, 128), None),
    ("layers/attn/wv", (16, 4096, 32, 128), None),
    ("layers/attn/wo", (16, 32, 128, 4096), None),
    ("layers/ffn/wu", (16, 4096, 16384), None),
    ("layers/ffn/wd", (16, 16384, 4096), None),
  ],
  "weight_macs": 3221225472,
  "attn_macs": [131072.0, 39321600.0, 1179713536.0],
  "decode_flops": [6880493568.0, 111011430400.0],
  "prefill_flops": [6854541312.0, 872549187584.0, 3333373558784.0],
  "fused_sparse_ffn": [
      (4294967296.0, 268697600.0),
      (805306368.0, 268484608.0),
      (268435456.0, 268451840.0),
  ],
  "paged_window_attention": [
      (83935232.0, 84197376.0),
      (1261568.0, 1310720.0),
      (16384.0, 32768.0),
  ],
}

LOGITS = {
  "tokens": [
      415, 43, 91, 121, 92, 410, 445, 298, 20, 48, 170, 221, 318, 245, 135,
      81, 354, 376, 16, 58, 231, 200, 454, 264, 215, 220, 341, 300, 88, 377,
      387, 489, 402, 145, 163, 332, 332, 356, 445, 149,
  ],
  "rows": [0, 17, 39],
  "ref": [
      [0.05937895178794861, 0.06758913397789001,
       -0.008679036051034927, -0.09800983965396881,
       0.2456302046775818, 0.26027271151542664],
      [0.17843037843704224, -0.10225332528352737,
       -0.2363181710243225, 0.04160044714808464,
       -0.004901579115539789, 0.19262267649173737],
      [-0.13733749091625214, -0.09324328601360321,
       -0.32613590359687805, 0.1054990366101265,
       0.23515690863132477, 0.40161770582199097],
  ],
  "ctrl": [
      [0.04481126368045807, 0.07043813169002533,
       -0.04072336480021477, -0.0831804946064949,
       0.21333135664463043, 0.26943981647491455],
      [0.1844986081123352, -0.11212809383869171,
       -0.22433330118656158, 0.051958464086055756,
       0.012027399614453316, 0.20767132937908173],
      [-0.1264764815568924, -0.09212802350521088,
       -0.32358020544052124, 0.12283461540937424,
       0.2210143357515335, 0.39169010519981384],
  ],
  "argmax": [148, 116, 277],
}


GOLDEN = {"opt-1.3b": OPT13, "opt-6.7b-16l": OPT67}
# decoding slots' contexts of three steps: 16 slots of 5123 keys in all,
# 3 of 77, 1 of 1
CONTEXTS = [[320] * 15 + [323], [25, 26, 26], [1]]
STEPS = [harness.StepRecord(0, 0, c, [], True, True) for c in CONTEXTS]
# the parent's OPT weights for rehearsal.MC: sha256 over every leaf's path
# and bytes, in path order
WEIGHTS_SHA256 = {
    2**31 + 5:
        "9a7822552bc21dcad039f86785d5813737ce7126675a2f1243bc75a8fb34bfef",
    2**33 + 17:
        "33db667cba89c55bb905c61793eae8fb60631e5a73063096a386790f31d85749",
}


@pytest.mark.parametrize("name", sorted(GOLDEN))
def test_opt_shapes_and_flops_are_the_parents(name):
    mc, want = CONFIGS[name], GOLDEN[name]
    spec = arch.load(mc).shapes(mc)
    got = [(k, tuple(v[0]), v[1]) for k, v in spec.items()]
    assert got == want["shapes"]
    # only the embedding is padded: its rows past the vocabulary
    assert [k for k, v in spec.items() if len(v) > 2] == ["embed"]
    axis, real = spec["embed"][2]
    assert axis == 0 and real.sum() == mc["vocab_size"] and real[
        :mc["vocab_size"]].all()
    assert flops.weight_macs(mc) == want["weight_macs"]
    assert [flops.attn_macs(mc, [c]) for c in (1, 300, 9000.5)] == (
        want["attn_macs"])
    assert [flops.decode_flops(mc, c) for c in ([100], CONTEXTS[0])
            ] == want["decode_flops"]
    assert [flops.prefill_flops(mc, s) for s in (1, 135, 512)] == (
        want["prefill_flops"])


@pytest.mark.parametrize("name", sorted(GOLDEN))
@pytest.mark.parametrize("kernel", ["fused_sparse_ffn",
                                    "paged_window_attention"])
def test_opt_roofline_work_is_the_parents(monkeypatch, name, kernel):
    ctx = report.Context.__new__(report.Context)
    ctx.mc, ctx.steps = CONFIGS[name], STEPS
    monkeypatch.setattr(readers, "roofline", lambda ctx, k, calls: (
        k, [calls(s) for s in ctx.steps]))
    got = report.module("metrics", f"{kernel}_roofline").read(ctx)
    layers = CONFIGS[name]["num_hidden_layers"]
    # every layer's call does the parent's work of one call
    assert got == (kernel, [[w] * layers for w in GOLDEN[name][kernel]])
    assert ctx.dims["calls"][kernel] == layers


@pytest.mark.parametrize("seed", sorted(WEIGHTS_SHA256))
def test_opt_weights_are_the_parents(seed):
    w = weights.make(rehearsal.MC, seed)
    h = hashlib.sha256()
    for path, x in sorted(jax.tree_util.tree_flatten_with_path(w)[0],
                          key=lambda t: jax.tree_util.keystr(t[0])):
        h.update(jax.tree_util.keystr(path).encode())
        h.update(np.asarray(x).tobytes())
    assert h.hexdigest() == WEIGHTS_SHA256[seed]


def test_windowed_layers_count_their_window(monkeypatch):
    # four layers of 2 heads of 8; the first attends fully, the other three
    # to their last 4 keys
    dm = {"n_layers": 4, "d_model": 16, "n_heads": 2, "n_kv_heads": 1,
          "head_dim": 8, "d_ff": 32, "n_proj": 3, "vocab_size": 64,
          "calls": {"fused_sparse_ffn": 4, "paged_window_attention": 4},
          "windows": [0, 4, 4, 4]}
    monkeypatch.setattr(arch, "dims", lambda mc: dm)
    per_key = 2 * 2 * 8  # score and value MACs of one key, both heads
    # decode: slots of 2 and 10 keys read 2 + 10 fully, 2 + 4 windowed
    assert flops.keys_per_layer({}, [2, 10]) == [12, 6, 6, 6]
    assert flops.attn_macs({}, [2, 10]) == per_key * (12 + 3 * 6)
    assert flops.attn_macs({}, [10]) == per_key * (10 + 3 * 4)
    weights_macs = 4 * (2 * 16 * 2 * 8 + 2 * 16 * 1 * 8 + 3 * 16 * 32)
    assert flops.decode_flops({}, [2, 10]) == 2 * (
        2 * (weights_macs + 16 * 64) + per_key * 30)
    # prefill of 6 positions: 1+2+...+6 = 21 keys fully, 1+2+3+4+4+4 = 18
    # in a window of 4; a window as long as the prompt reads all 21
    assert flops.prefill_flops({}, 6) == 2 * (
        6 * weights_macs + per_key * (21 + 3 * 18) + 16 * 64)
    # each layer's attention call reads its own window's keys
    ctx = report.Context.__new__(report.Context)
    ctx.mc, ctx.steps = {}, [harness.StepRecord(0, 0, [2, 10], [], True,
                                                True)]
    monkeypatch.setattr(readers, "roofline", lambda ctx, k, calls: [
        calls(s) for s in ctx.steps])
    att = report.module("counts", "paged_window_attention")
    got = report.module("metrics", "paged_window_attention_roofline").read(
        ctx)
    assert got == [[att.work(tokens=2, context_tokens=k, n_heads=2,
                             head_dim=8, n_kv_heads=1)
                    for k in (12, 6, 6, 6)]]
    dm["windows"] = [0, 6, 6, 6]
    assert flops.prefill_flops({}, 6) == 2 * (
        6 * weights_macs + per_key * 4 * 21 + 16 * 64)
    dm["windows"] = [0, 6, 6]  # one short of the layers
    with pytest.raises(ValueError, match="3 windows for 4 layers"):
        flops.prefill_flops({}, 6)


@pytest.mark.parametrize("control", [False, True])
def test_opt_reference_logits_are_the_parents(control):
    mc = rehearsal.MC
    w = weights.make(mc, 2**31 + 5)
    tokens = np.zeros(64, np.int32)
    tokens[:40] = LOGITS["tokens"]
    rows = np.zeros(64, np.int32)
    rows[:3] = LOGITS["rows"]
    got = arch.load(mc).logits_at(w, jnp.asarray(tokens), jnp.asarray(rows),
                                  mc=mc, control=control)
    want = LOGITS["ctrl" if control else "ref"]
    np.testing.assert_allclose(np.asarray(got[:3, :6]), want, rtol=0,
                               atol=1e-6)
    if not control:
        assert np.asarray(jnp.argmax(got[:3], -1)).tolist() == (
            LOGITS["argmax"])


def test_second_architecture_runs_correct(monkeypatch):
    rehearsal.use_arch(monkeypatch, arch_gqa_glu)
    raw = rehearsal.run(monkeypatch, SEED, mc=GQA, cell=GQA_CELL,
                        control=True)
    line = report.result(raw, trace=False)
    assert line["correct"] is True
    assert line["compared"]["tokens_checked"]["value"] >= (
        harness.CHECK_TOKENS)
    assert raw["check"]["slots_checked"] == rehearsal.CELL["n_slots"]
    limit = GQA_CELL["mean_logit_gap_limit"]
    assert raw["check"]["control_mean_logit_gap"] > limit


def test_second_architecture_flops_follow_its_dims(monkeypatch):
    # 2 layers of d 128: q and out over 16 heads of 8, k and v over 4,
    # three FFN projections of 256
    rehearsal.use_arch(monkeypatch, arch_gqa_glu)
    assert flops.weight_macs(GQA) == 2 * (2 * 128 * 128 + 2 * 128 * 32
                                          + 3 * 128 * 256)
    assert flops.attn_macs(GQA, [10]) == 2 * 2.0 * 128 * 10


@pytest.mark.parametrize("fault", rehearsal.FAULTS)
def test_second_architecture_fault_is_not_correct(monkeypatch, fault):
    rehearsal.use_arch(monkeypatch, arch_gqa_glu)
    raw = rehearsal.run(monkeypatch, SEED, mc=GQA, cell=GQA_CELL,
                        fault=fault)
    assert report.result(raw, trace=False)["correct"] is False


def test_padded_heads_are_zero_in_the_programs_slots(monkeypatch):
    rehearsal.use_arch(monkeypatch, arch_gqa_glu)
    w = weights.make(PADDED, SEED)
    att = w["layers"]["attn"]
    wq = np.asarray(att["wq"], np.float32)
    wo = np.asarray(att["wo"], np.float32)
    pad = np.arange(16) % 4 == 3  # slot 3 of each KV head's group of 4
    assert wq.shape[2] == wo.shape[1] == 16
    assert not wq[:, :, pad].any() and not wo[:, pad].any()
    assert (np.abs(wq[:, :, ~pad]).max(axis=(0, 1, 3)) > 0).all()
    assert (np.abs(wo[:, ~pad]).max(axis=(0, 2, 3)) > 0).all()
    for leaf in ("embed", "unembed"):
        rows = np.asarray(w[leaf], np.float32)
        assert not rows[PADDED["vocab_size"]:].any()
        assert np.abs(rows[:PADDED["vocab_size"]]).min(axis=1).any()


def test_padded_heads_run_correct(monkeypatch):
    rehearsal.use_arch(monkeypatch, arch_gqa_glu)
    raw = rehearsal.run(monkeypatch, SEED, mc=PADDED, cell=GQA_CELL,
                        control=True)
    assert report.result(raw, trace=False)["correct"] is True
    assert raw["check"]["tokens_checked"] >= harness.CHECK_TOKENS
    limit = GQA_CELL["mean_logit_gap_limit"]
    assert raw["check"]["control_mean_logit_gap"] > limit


@pytest.mark.parametrize("fault", rehearsal.FAULTS)
def test_padded_heads_fault_is_not_correct(monkeypatch, fault):
    rehearsal.use_arch(monkeypatch, arch_gqa_glu)
    raw = rehearsal.run(monkeypatch, SEED, mc=PADDED, cell=GQA_CELL,
                        fault=fault)
    assert report.result(raw, trace=False)["correct"] is False


def test_padding_in_other_slots_is_not_correct(monkeypatch):
    """Weights padded in the last four slots ("the first 12 heads are
    real") are not the layout the program masks: the reference then
    computes another model, and the run is not correct."""
    rehearsal.use_arch(monkeypatch, arch_gqa_glu)
    monkeypatch.setattr(arch_gqa_glu, "head_slots", lambda h, k: (
        16, np.arange(16) < h, k, np.ones(k, bool)))
    raw = rehearsal.run(monkeypatch, SEED, mc=PADDED, cell=GQA_CELL)
    assert report.result(raw, trace=False)["correct"] is False


@pytest.mark.parametrize("mc, match", [
    ({k: v for k, v in CONFIGS["opt-1.3b"].items() if k != "model_type"},
     "no 'model_type'"),
    (dict(CONFIGS["opt-1.3b"], model_type="falcon"), "bench/arch/falcon.py"),
    (dict(CONFIGS["opt-1.3b"], model_type="../harness"), "'model_type'"),
], ids=["missing", "no-module", "not-a-name"])
def test_refuses_a_file_without_an_architecture(mc, match):
    with pytest.raises(ValueError, match=match):
        harness.program_config(mc)


# the file at the registry's own sizes for "tiny-relu"
TINY_RELU = dict(GQA, hidden_size=64, num_attention_heads=4,
                 num_key_value_heads=2, intermediate_size=256,
                 vocab_size=256)


@pytest.mark.parametrize("mc, differs", [
    (CONFIGS["opt-1.3b"], None),
    (dict(CONFIGS["opt-1.3b"], registry_name="opt-2.7b"), "d_model"),
    (TINY_RELU, None),
    (dict(TINY_RELU, num_key_value_heads=3), "n_kv_heads"),
    (dict(TINY_RELU, rope_theta=5e5), "rope_theta"),
], ids=["opt", "opt-other-model", "gqa", "gqa-kv-heads", "gqa-rope"])
def test_program_fields_are_checked_against_the_registry(monkeypatch, mc,
                                                         differs):
    rehearsal.use_arch(monkeypatch, arch_gqa_glu)
    if differs is None:
        cfg = harness.program_config(mc)
        assert cfg.n_layers == mc["num_hidden_layers"]
        return
    with pytest.raises(ValueError, match=differs):
        harness.program_config(mc)
