"""Compile the decode-path Pallas kernels for a described TPU v5e.

Nothing here runs on a chip: the TPU compiler, which ships with jax, lowers
and compiles each kernel for a v5e that is described, not attached, at the
widths the engine serves (OPT-1.3B for the MLP family, Qwen3-4B for GLU,
OPT-1.3B's attention geometry) with T = 8 slots. A refusal here (a block
shape off the (8, 128) tiling, more VMEM than a kernel may use) is exactly
what the chip's compiler would raise, caught before any chip run.

The topology is described inside a fixture, never at import: only one
process may hold the TPU library, and describing it at collection time would
let pytest-xdist workers collect different tests.
"""
from __future__ import annotations

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import fused_decode as kfd
from repro.kernels import paged_attention as kpa
from repro.kernels import sparse_matmul as ksm

T = 8          # decode slots
TILE = 128     # FFN gather tile (the TPU lane width)
OPT = dict(d=2048, F=8192)    # opt-1.3b MLP widths
QWEN3 = dict(d=2560, F=9728)  # qwen3-4b GLU widths
ATTN = dict(kvp=32, hd=64, bs=16, n_blocks=512, nb=32)  # opt-1.3b, 512 tok


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip can be written to the persistent cache
    # but never read back without one: keep the cache out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)


def _compile_text(fn, shapes, sharding):
    args = [jax.ShapeDtypeStruct(s, dt, sharding=sharding) for s, dt in shapes]
    return jax.jit(fn).lower(*args).compile().as_text()


def _ffn_case(case):
    w = QWEN3 if case == "glu" else OPT
    d, F = w["d"], w["F"]
    K = F // TILE
    bf, i32 = jnp.bfloat16, jnp.int32
    shapes = [((T, d), bf), ((d, F), bf), ((F, d), bf), ((T, K), i32),
              ((T,), i32)]
    if case == "glu":
        shapes.append(((d, F), bf))

        def fn(x, wg, wd, idx, nv, wu):
            return kfd.fused_sparse_ffn(x, wg, wd, idx, nv, w_up=wu,
                                        activation="silu", tile=TILE,
                                        interpret=False)
    elif case == "mlp_unit_mask":
        shapes.append(((T, F), jnp.bool_))

        def fn(x, wg, wd, idx, nv, mask):
            return kfd.fused_sparse_ffn(x, wg, wd, idx, nv, unit_mask=mask,
                                        tile=TILE, interpret=False)
    else:
        def fn(x, wg, wd, idx, nv):
            return kfd.fused_sparse_ffn(x, wg, wd, idx, nv, tile=TILE,
                                        interpret=False)
    return fn, shapes


@pytest.mark.parametrize("case", ["mlp", "glu", "mlp_unit_mask"])
def test_fused_sparse_ffn_compiles_for_v5e(one_chip, case):
    fn, shapes = _ffn_case(case)
    text = _compile_text(fn, shapes, one_chip)
    assert "tpu_custom_call" in text
    assert "fused_sparse_ffn" in text


@pytest.mark.parametrize("W", [1, 5])
def test_paged_window_attention_compiles_for_v5e(one_chip, W):
    a = ATTN
    bf, i32 = jnp.bfloat16, jnp.int32
    pool = (a["n_blocks"], a["kvp"], a["bs"], a["hd"])
    shapes = [((T, W, a["kvp"], 1, a["hd"]), bf), (pool, bf), (pool, bf),
              ((T, a["nb"]), i32), ((T, W), i32)]

    def fn(q, k, v, table, pos):
        return kpa.paged_window_attention(q, k, v, table, pos,
                                          interpret=False)

    text = _compile_text(fn, shapes, one_chip)
    assert "tpu_custom_call" in text
    assert "paged_window_attention" in text


def test_sparse_matmul_tokens_compiles_for_v5e(one_chip):
    d, F = OPT["d"], OPT["F"]
    bf, i32 = jnp.bfloat16, jnp.int32
    shapes = [((T, F), bf), ((F, d), bf), ((T, F // TILE), i32), ((T,), i32)]

    def fn(h, wd, idx, nv):
        return ksm.sparse_matmul_tokens(h, wd, idx, nv, tile=TILE,
                                        interpret=False)

    text = _compile_text(fn, shapes, one_chip)
    assert "tpu_custom_call" in text
    assert "sparse_matmul_tokens" in text


def test_sparse_up_matmul_compiles_for_v5e(one_chip):
    d, F = OPT["d"], OPT["F"]
    bf, i32 = jnp.bfloat16, jnp.int32
    shapes = [((T, d), bf), ((d, F), bf), ((T, F // TILE), i32), ((T,), i32)]

    def fn(x, wu, idx, nv):
        return ksm.sparse_up_matmul(x, wu, idx, nv, tile=TILE,
                                    interpret=False)

    text = _compile_text(fn, shapes, one_chip)
    assert "tpu_custom_call" in text
    assert "sparse_up_matmul" in text
