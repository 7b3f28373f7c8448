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

import math
import os
import re

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
    pool = (2, a["n_blocks"], a["kvp"], a["bs"], a["hd"])
    shapes = [((T, W, a["kvp"], 1, a["hd"]), bf), (pool, bf), (pool, bf),
              ((T, a["nb"]), i32), ((T, W), i32), ((), i32)]

    def fn(q, k, v, table, pos, layer):
        return kpa.paged_window_attention(q, k, v, table, pos, layer,
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


# ---------------------------------------------------------------------------
# whole serving programs: the KV pool is touched only in place


STEP_SLOTS, STEP_BS, STEP_BLOCKS = 16, 64, 257  # the decode-batch cells


def _step_cfg(width):
    """Two layers at OPT-1.3B (head_dim 64) or OPT-6.7B (head_dim 128)
    attention widths. The 6.7B case runs a d_model-wide FFN: at d_ff 16384
    the layer scan's per-layer FFN weight slices alone (a separate cost)
    outweigh one layer of the pool, the bound checked below."""
    from repro.configs import get_config

    if width == "hd64":
        return get_config("opt-1.3b").replace(n_layers=2)
    return get_config("opt-6.7b").replace(n_layers=2, d_ff=4096)


def _step_program(cfg, program, sharding):
    """(fn, argument shapes, donated index) of one jitted serving program
    on the fast path's pool; params in bf16, as the engine serves them."""
    from repro.models import transformer as tf

    def sds(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt, sharding=sharding)

    params = jax.tree.map(
        lambda x: sds(x.shape, jnp.bfloat16 if x.dtype == jnp.float32
                      else x.dtype),
        jax.eval_shape(lambda: tf.init_params(jax.random.PRNGKey(0), cfg)))
    pool = sds(_pool_shape(cfg), jnp.bfloat16)
    pages = {"k": pool, "v": pool}
    b, bs, i32 = STEP_SLOTS, STEP_BS, jnp.int32
    nb = 1024 // bs
    masks = sds((cfg.n_layers, b, cfg.d_ff), jnp.bool_)
    refresh = sds((b,), jnp.bool_)
    if program == "decode":
        def fn(params, pages, table, tok, pos, masks, refresh):
            return tf.decode_step_paged(params, pages, table, tok, pos, cfg,
                                        masks, refresh, block_size=bs,
                                        fast_kernels=True)
        args = (params, pages, sds((b, nb), i32), sds((b,), i32),
                sds((b,), i32), masks, refresh)
        return fn, args, 1
    if program == "window":  # γ = 4 verify; chunked prefill and the draft
        def fn(params, pages, table, tok, pos0, wlen, masks, refresh):
            return tf.verify_window_paged(params, pages, table, tok, pos0,
                                          wlen, cfg, masks, refresh,
                                          block_size=bs, fast_kernels=True)
        args = (params, pages, sds((b, nb), i32), sds((b, 5), i32),
                sds((b,), i32), sds((b,), i32), masks, refresh)
        return fn, args, 1

    def fn(params, tokens, pages, blocks, true_len):  # whole-prompt prefill
        return tf.prefill_paged(params, tokens, cfg, pages, blocks,
                                block_size=bs, true_len=true_len)
    args = (params, sds((1, 256), i32), pages, sds((256 // bs,), i32),
            sds((), i32))
    return fn, args, 2


def _pool_shape(cfg):
    return (cfg.n_layers, STEP_BLOCKS, cfg.n_kv_heads, STEP_BS,
            kpa.pool_head_dim(cfg.resolved_head_dim))


@pytest.mark.parametrize("program", ["decode", "window", "prefill"])
@pytest.mark.parametrize("width", ["hd64", "hd128"])
def test_serving_program_touches_pool_in_place_for_v5e(one_chip, monkeypatch,
                                                       width, program):
    """Each program the engine runs on the fast path's pool (rows in whole
    128-lane tiles), compiled for a v5e in the device's default layouts,
    neither relayouts the pool nor slices a layer out of it: no copy or
    dynamic-slice of the pool's shape or of one layer's, and less scratch
    memory than one layer's K."""
    # the program picks compiled kernels from the backend it runs on; the
    # described chip is not the default backend here
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    cfg = _step_cfg(width)
    fn, args, donated = _step_program(cfg, program, one_chip)
    compiled = jax.jit(fn, donate_argnums=(donated,)).lower(*args).compile()
    text = compiled.as_text()
    pool = _pool_shape(cfg)
    layer = ",".join(map(str, pool[1:]))
    shapes = {",".join(map(str, pool)), layer, "1," + layer}
    moved = []
    for m in re.finditer(r"%([\w.-]+) = \w+\[([\d,]*)\]\S* ([\w-]+)\(",
                         text):
        name, dims, op = m.groups()
        if dims in shapes and (op in ("copy", "copy-start", "dynamic-slice",
                                      "transpose")
                               or "copy" in name or "dynamic-slice" in name):
            moved.append(f"{name} {op}")
    assert not moved, moved
    one_layer_k = math.prod(pool[1:]) * 2
    assert compiled.memory_analysis().temp_size_in_bytes < one_layer_k
    if program != "prefill":  # whole-prompt prefill runs no kernel
        assert "paged_kv_write" in text and "paged_window_attention" in text


@pytest.mark.parametrize("width", ["hd64", "hd128"])
def test_decode_reads_ffn_weights_in_place_for_v5e(one_chip, monkeypatch,
                                                   width):
    """The fast decode step hands the fused FFN kernel the layer scan's
    whole weight stacks: no layer's (d_model, d_ff) or (d_ff, d_model)
    projection is sliced or copied out of them first, so the kernel's own
    DMAs are the step's only FFN weight reads."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    cfg = _step_cfg(width)
    fn, args, donated = _step_program(cfg, "decode", one_chip)
    text = jax.jit(fn, donate_argnums=(donated,)).lower(*args).compile(
    ).as_text()
    d, f = cfg.d_model, cfg.d_ff
    shapes = {f"{d},{f}", f"{f},{d}", f"1,{d},{f}", f"1,{f},{d}"}
    moved = [f"{name} {op}" for name, dims, op in re.findall(
                 r"%([\w.-]+) = \w+\[([\d,]*)\]\S* ([\w-]+)\(", text)
             if dims in shapes and ("copy" in name or "dynamic-slice" in name
                                    or op in ("copy", "dynamic-slice"))]
    assert not moved, moved
    assert "fused_sparse_ffn" in text
