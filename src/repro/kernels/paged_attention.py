"""Paged window-attention kernel: block-table gather INSIDE the kernel.

The frozen serving path materializes every slot's whole gathered cache each
step (``common.paged_gather``: pool[table] -> (b, kvp, nb*bs, hd)) before a
dense-softmax attention reads it once — 2x the cache traffic, plus an HBM
round-trip for a tensor that exists only to be immediately consumed. This
kernel reads the K/V pool THROUGH the block table instead: the table rides
in as scalar prefetch, the K/V BlockSpecs dereference ``table[t, j]``, and
the DMA engine streams each slot's blocks straight from the pool into VMEM
— one read of exactly the blocks a slot owns, no gathered copy.

Softmax is the online (flash-decode) form over the block axis: running
(m, l, acc) scratch carried across the inner grid dimension, finalized on
the last block. Numerically this is the textbook-exact rewrite of the
frozen full-softmax ``window_attention`` — greedy token streams match at
f32 (tests/test_fused_decode.py); individual logits may differ in the last
ulp, which is the same contract the chunked ``flash_attention`` already
ships under.

Handles both serving shapes: W = 1 plain decode and the W = γ+1
speculative-verification window (causal within the window via per-token
positions), plus GQA (all kv heads batched per block) and the optional
sliding window. Block-table padding rows point at the scratch block; their
keys sit past every real position and mask to zero weight exactly as the
materialized path's ``pos`` masking did.
"""
from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.runtime import resolve_interpret


def _make_kernel(bs: int, window: int):
    def kernel(tbl_ref, q_ref, k_ref, v_ref, pos_ref, o_ref,
               m_ref, l_ref, acc_ref):
        j = pl.program_id(1)
        nb = pl.num_programs(1)

        @pl.when(j == 0)
        def _init():
            m_ref[...] = jnp.full_like(m_ref, -1e30)
            l_ref[...] = jnp.zeros_like(l_ref)
            acc_ref[...] = jnp.zeros_like(acc_ref)

        q = q_ref[0]  # (kvp, Wg, hd) — pre-scaled, cache dtype
        k = k_ref[0]  # (kvp, bs, hd) block table[t, j] of the pool
        v = v_ref[0]
        logits = jax.lax.dot_general(  # (kvp, Wg, bs)
            q, k, (((2,), (2,)), ((0,), (0,))),
            preferred_element_type=jnp.float32)
        qpos = pos_ref[0]  # (Wg, 1) int32 absolute position per query row
        kpos = j * bs + jax.lax.broadcasted_iota(jnp.int32, (q.shape[1], bs),
                                                 1)
        valid = kpos <= qpos
        if window:
            valid &= kpos > qpos - window
        logits = jnp.where(valid[None], logits, -1e30)
        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, jnp.max(logits, axis=-1))
        # explicit zeroing of masked probabilities: a block whose keys are
        # ALL masked for some row (sliding window past the head of the
        # cache) must contribute nothing even while m is still -1e30
        p = jnp.where(valid[None], jnp.exp(logits - m_new[..., None]), 0.0)
        corr = jnp.exp(m_prev - m_new)
        l_ref[...] = l_ref[...] * corr + jnp.sum(p, axis=-1)
        acc_ref[...] = acc_ref[...] * corr[..., None] + jax.lax.dot_general(
            p.astype(v.dtype), v, (((2,), (1,)), ((0,), (0,))),
            preferred_element_type=jnp.float32)
        m_ref[...] = m_new

        @pl.when(j == nb - 1)
        def _finalize():
            o_ref[0] = acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)[..., None]

    return kernel


@functools.partial(jax.jit, static_argnames=("window", "interpret"))
def paged_window_attention(q, k_pages, v_pages, table, pos, *,
                           window: int = 0,
                           interpret: Optional[bool] = None):
    """Windowed grouped attention straight off the paged pool.

    q: (b, W, kvp, g, hd) the W-token query window per slot; k_pages /
    v_pages: (n_blocks, kvp, bs, hd) ONE layer's pool (head-major blocks);
    table: (b, nb) int32 block ids in sequence order (pads -> scratch
    block); pos: (b, W) absolute position of each window token. Causal
    within the window: query i attends to cache positions <= pos[:, i].
    Returns (b, W, kvp, g, hd) in q's dtype — drop-in for
    ``paged_gather`` + ``window_attention``.
    """
    b, W, kvp, g, hd = q.shape
    n_blocks, _, bs, _ = k_pages.shape
    nb = table.shape[1]
    scale = 1.0 / math.sqrt(hd)
    # mirror the frozen path's rounding placement: scale in q dtype, then
    # compute logits in the cache dtype with f32 accumulation
    qs = (q * jnp.asarray(scale, q.dtype)).astype(k_pages.dtype)
    qs = qs.transpose(0, 2, 1, 3, 4).reshape(b, kvp, W * g, hd)
    # (b, W*g, 1): a column per slot, so the block's last two dims equal the
    # array's (TPU lowering rule) and it broadcasts against the key
    # positions without a relayout
    posr = jnp.repeat(pos.astype(jnp.int32), g, axis=1)[:, :, None]

    spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(b, nb),
        in_specs=[
            pl.BlockSpec((1, kvp, W * g, hd), lambda t, j, tbl: (t, 0, 0, 0)),
            pl.BlockSpec((1, kvp, bs, hd),
                         lambda t, j, tbl: (tbl[t, j], 0, 0, 0)),
            pl.BlockSpec((1, kvp, bs, hd),
                         lambda t, j, tbl: (tbl[t, j], 0, 0, 0)),
            pl.BlockSpec((1, W * g, 1), lambda t, j, tbl: (t, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, kvp, W * g, hd),
                               lambda t, j, tbl: (t, 0, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((kvp, W * g), jnp.float32),
            pltpu.VMEM((kvp, W * g), jnp.float32),
            pltpu.VMEM((kvp, W * g, hd), jnp.float32),
        ],
    )
    o = pl.pallas_call(
        _make_kernel(bs, window),
        grid_spec=spec,
        name="paged_window_attention",
        out_shape=jax.ShapeDtypeStruct((b, kvp, W * g, hd), jnp.float32),
        interpret=resolve_interpret(interpret),
    )(table.astype(jnp.int32), qs, k_pages, v_pages, posr)
    o = o.reshape(b, kvp, W, g, hd).transpose(0, 2, 1, 3, 4)
    return o.astype(q.dtype)


def paged_decode_attention(q, k_pages, v_pages, table, pos, *,
                           window: int = 0,
                           interpret: Optional[bool] = None):
    """W = 1 decode specialization. q: (b, kvp, g, hd); pos: (b,)."""
    return paged_window_attention(q[:, None], k_pages, v_pages, table,
                                  pos[:, None], window=window,
                                  interpret=interpret)[:, 0]


def modeled_cache_bytes(nb: int, bs: int, kvp: int, hd: int,
                        itemsize: int) -> float:
    """HBM bytes ONE slot's attention reads per layer through this kernel:
    each owned K and V block streamed exactly once (the materialized
    ``paged_gather`` path pays this twice — once building the gathered
    copy, once reading it — plus the copy's write)."""
    return 2.0 * nb * bs * kvp * hd * itemsize
