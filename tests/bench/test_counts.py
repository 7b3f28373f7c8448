"""Kernel work counts and the peaks table, checked against shapes."""
import json

import pytest

from bench import peaks, report
from bench.spec import BENCH

OPT13 = json.loads((BENCH / "configs" / "opt-1.3b.json").read_text())


@pytest.mark.parametrize("tokens", [1, 8, 32])
def test_ffn_reads_named_tiles_once_whatever_the_batch(tokens):
    ffn = report.module("counts", "fused_sparse_ffn")
    flops, nbytes = ffn.work(tokens=tokens, d_model=2048, tile=128,
                             tiles_named=64, tile_token_pairs=tokens * 64)
    weights = 2 * 2048 * 8192 * 2  # up and down projections, bf16, once
    assert nbytes == weights + 2 * tokens * 2048 * 2
    assert flops == 2.0 * tokens * 2 * 2048 * 8192


def test_ffn_counts_only_named_tiles():
    ffn = report.module("counts", "fused_sparse_ffn")
    _, all_b = ffn.work(tokens=4, d_model=4096, tile=128, tiles_named=128,
                        tile_token_pairs=512)
    f, half_b = ffn.work(tokens=4, d_model=4096, tile=128, tiles_named=64,
                         tile_token_pairs=100)
    act = 2 * 4 * 4096 * 2
    assert half_b - act == (all_b - act) / 2
    assert f == 2.0 * 100 * 2 * 128 * 4096


def test_attention_reads_valid_context_once():
    att = report.module("counts", "paged_window_attention")
    flops, nbytes = att.work(tokens=2, context_tokens=300, n_heads=32,
                             head_dim=64)
    assert nbytes == 2 * 300 * 32 * 64 * 2 + 2 * 2 * 32 * 64 * 2
    assert flops == 4.0 * 300 * 32 * 64


@pytest.mark.parametrize("n_kv_heads", [32, 8, 1])
def test_attention_reads_the_kv_heads_it_has(n_kv_heads):
    att = report.module("counts", "paged_window_attention")
    shape = dict(tokens=2, context_tokens=300, n_heads=32, head_dim=64)
    mha_flops, mha_bytes = att.work(**shape)
    flops, nbytes = att.work(**shape, n_kv_heads=n_kv_heads)
    qo = 2 * 2 * 32 * 64 * 2
    assert flops == mha_flops
    assert nbytes - qo == (mha_bytes - qo) * n_kv_heads / 32


def test_gated_ffn_reads_and_multiplies_three_projections():
    ffn = report.module("counts", "fused_sparse_ffn")
    shape = dict(tokens=4, d_model=4096, tile=128, tiles_named=100,
                 tile_token_pairs=300)
    f2, b2 = ffn.work(**shape)
    f3, b3 = ffn.work(**shape, n_proj=3)
    act = 2 * 4 * 4096 * 2
    assert b3 - act == 1.5 * (b2 - act)
    assert f3 == 1.5 * f2


def test_peaks_table():
    pk = peaks.peaks("TPU v5 lite")
    assert pk["flops_per_s"] == 197e12 and pk["hbm_bytes_per_s"] == 819e9
    src = json.loads((BENCH / "peaks.json").read_text())["source"]
    assert "TPU v5e" in src
    with pytest.raises(KeyError):
        peaks.peaks("TPU v4")


def test_roofline_names_its_bound():
    pk = peaks.peaks("TPU v5 lite")
    ffn = report.module("counts", "fused_sparse_ffn")
    t, bound = peaks.roofline_seconds(*ffn.work(
        tokens=32, d_model=2048, tile=128, tiles_named=64,
        tile_token_pairs=32 * 64), pk)
    assert bound == "memory"
    assert abs(t - (64 * 2**20 + 2 * 32 * 2048 * 2) / 819e9) < 1e-12
    t, bound = peaks.roofline_seconds(197e12, 1.0, pk)
    assert (t, bound) == (1.0, "compute")


def test_model_flops_per_token():
    from bench import flops
    # weight MACs of OPT-1.3B: 24 x (4 d^2 + 2 d F) = 1.2 G
    assert flops.weight_macs(OPT13) == 24 * (4 * 2048**2 + 2 * 2048 * 8192)
    one = flops.decode_flops(OPT13, [100])
    assert one == 2 * (flops.weight_macs(OPT13) + 2048 * 50272
                       + 24 * 2 * 2048 * 100)
    assert flops.prefill_flops(OPT13, 1) == 2 * (
        flops.weight_macs(OPT13) + 24 * 2 * 2048 + 2048 * 50272)
