"""Paged KV-pool kernels: attention reads, and token writes, in place.

The pool is ONE stacked buffer per K and V, (L, n_blocks, kvp, bs, hd'),
head-major within a block, that every serving program takes and returns.
Both kernels here touch it only where a layer's blocks lie, addressed by a
traced layer index that rides in as scalar prefetch: no per-layer slice of
the pool and no relayout of it is ever materialized. Its rows are whole
128-lane tiles, hd' = ``pool_head_dim(hd)`` (the model's values, then
zeros): a narrower row would let the TPU keep the pool in another layout
between programs (at head_dim 64 it puts the block axis minor-most), and
every program would then relayout the whole pool on entry and on exit.
The kernels read 128-lane tiles either way.

``paged_window_attention`` reads the pool THROUGH the block table. The
frozen serving path materializes every slot's whole gathered cache each step
(``common.paged_gather``: pool[layer][table] -> (b, kvp, nb*bs, hd)) before a
dense-softmax attention reads it once — 2x the cache traffic, plus an HBM
round-trip for a tensor that exists only to be immediately consumed. Here the
table rides in as scalar prefetch, the K/V BlockSpecs dereference
``(layer, table[t, j])``, and the DMA engine streams each slot's blocks
straight from the pool into VMEM — one read of exactly the blocks a slot
owns, no gathered copy.

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

``paged_kv_write`` stores W >= 1 window tokens' K or V rows into the pool,
aliased in place — the fast-path replacement of ``common.paged_write_window``'s
XLA scatter, whose preferred layout would force a relayout of the whole pool
around every program.
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
    def kernel(tbl_ref, lay_ref, q_ref, k_ref, v_ref, pos_ref, o_ref,
               m_ref, l_ref, acc_ref):
        j = pl.program_id(1)
        nb = pl.num_programs(1)

        @pl.when(j == 0)
        def _init():
            m_ref[...] = jnp.full_like(m_ref, -1e30)
            l_ref[...] = jnp.zeros_like(l_ref)
            acc_ref[...] = jnp.zeros_like(acc_ref)

        q = q_ref[0]  # (kvp, Wg, hd) — pre-scaled, cache dtype
        k = k_ref[0, 0]  # (kvp, bs, hd) block table[t, j] of the layer
        v = v_ref[0, 0]
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
def paged_window_attention(q, k_pages, v_pages, table, pos, layer, *,
                           window: int = 0,
                           interpret: Optional[bool] = None):
    """Windowed grouped attention straight off the stacked paged pool.

    q: (b, W, kvp, g, hd) the W-token query window per slot; k_pages /
    v_pages: (L, n_blocks, kvp, bs, hd') the whole pool (head-major blocks,
    rows hd' >= hd wide, zeros past hd);
    table: (b, nb) int32 block ids in sequence order (pads -> scratch
    block); pos: (b, W) absolute position of each window token; layer:
    scalar (may be traced) — the layer whose blocks are read. Causal within
    the window: query i attends to cache positions <= pos[:, i]. Returns
    (b, W, kvp, g, hd) in q's dtype — drop-in for slicing the layer, then
    ``paged_gather`` + ``window_attention``.
    """
    b, W, kvp, g, hd = q.shape
    bs, hdp = k_pages.shape[3:]
    nb = table.shape[1]
    scale = 1.0 / math.sqrt(hd)
    # mirror the frozen path's rounding placement: scale in q dtype, then
    # compute logits in the cache dtype with f32 accumulation; zero lanes
    # up to the pool's row width add exact zeros
    qs = (q * jnp.asarray(scale, q.dtype)).astype(k_pages.dtype)
    qs = qs.transpose(0, 2, 1, 3, 4).reshape(b, kvp, W * g, hd)
    qs = jnp.pad(qs, ((0, 0), (0, 0), (0, 0), (0, hdp - hd)))
    # (b, W*g, 1): a column per slot, so the block's last two dims equal the
    # array's (TPU lowering rule) and it broadcasts against the key
    # positions without a relayout
    posr = jnp.repeat(pos.astype(jnp.int32), g, axis=1)[:, :, None]
    kv_spec = pl.BlockSpec((1, 1, kvp, bs, hdp),
                           lambda t, j, tbl, lay: (lay[0], tbl[t, j], 0, 0, 0))

    spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b, nb),
        in_specs=[
            pl.BlockSpec((1, kvp, W * g, hdp),
                         lambda t, j, tbl, lay: (t, 0, 0, 0)),
            kv_spec,
            kv_spec,
            pl.BlockSpec((1, W * g, 1), lambda t, j, tbl, lay: (t, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, kvp, W * g, hdp),
                               lambda t, j, tbl, lay: (t, 0, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((kvp, W * g), jnp.float32),
            pltpu.VMEM((kvp, W * g), jnp.float32),
            pltpu.VMEM((kvp, W * g, hdp), jnp.float32),
        ],
    )
    o = pl.pallas_call(
        _make_kernel(bs, window),
        grid_spec=spec,
        name="paged_window_attention",
        out_shape=jax.ShapeDtypeStruct((b, kvp, W * g, hdp), jnp.float32),
        interpret=resolve_interpret(interpret),
    )(table.astype(jnp.int32), _layer_operand(layer), qs, k_pages, v_pages,
      posr)
    o = o[..., :hd].reshape(b, kvp, W, g, hd).transpose(0, 2, 1, 3, 4)
    return o.astype(q.dtype)


def paged_decode_attention(q, k_pages, v_pages, table, pos, layer, *,
                           window: int = 0,
                           interpret: Optional[bool] = None):
    """W = 1 decode specialization. q: (b, kvp, g, hd); pos: (b,)."""
    return paged_window_attention(q[:, None], k_pages, v_pages, table,
                                  pos[:, None], layer, window=window,
                                  interpret=interpret)[:, 0]


def pool_head_dim(head_dim: int) -> int:
    """Row width of the kernels' pool: ``head_dim`` in whole 128-lane
    tiles."""
    return -(-head_dim // 128) * 128


def _layer_operand(layer):
    """A layer index as the (1,) int32 scalar-prefetch operand."""
    return jnp.reshape(jnp.asarray(layer, jnp.int32), (1,))


def _make_write_kernel(rows: int):
    def kernel(lay_ref, blk_ref, off_ref, ord_ref, val_ref, pin_ref, o_ref):
        del lay_ref, ord_ref
        i = pl.program_id(0)
        prev = jnp.maximum(i - 1, 0)
        off = off_ref[i]

        # tokens arrive sorted by destination group, so a group's tokens
        # are consecutive grid steps and its output block stays resident
        # across them: load the pool's group on its first token only
        @pl.when((i == 0) | (blk_ref[i] != blk_ref[prev])
                 | (off // rows != off_ref[prev] // rows))
        def _load():
            o_ref[...] = pin_ref[...]

        grp = o_ref[0, 0]  # (kvp, rows, hd)
        row = jax.lax.broadcasted_iota(jnp.int32, grp.shape, 1)
        o_ref[0, 0] = jnp.where(row == off % rows,
                                jnp.broadcast_to(val_ref[0], grp.shape), grp)

    return kernel


@functools.partial(jax.jit, static_argnames=("interpret",))
def paged_kv_write(pages, val, layer, blk, off, *,
                   interpret: Optional[bool] = None):
    """Store W >= 1 window tokens' K or V rows into the stacked pool, in place.

    pages: (L, n_blocks, kvp, bs, hd'); val: (b, W, kvp, hd), hd <= hd'
    (rows zero-padded to the pool's width); layer: scalar (may be traced);
    blk, off: (b, W) destination block id and row within
    the block of each token (the caller routes disabled tokens and idle
    slots to the scratch block). Returns the pool with
    ``pages[layer, blk[t, w], :, off[t, w], :] = val[t, w]`` — equal to
    that scatter, duplicates included: tokens bound for one destination are
    applied in token order, the last one kept.

    The pool is aliased to the output and only the aligned row groups the
    tokens land in move: one grid step per token, each reading and writing
    its (kvp, rows, hd) group, ``rows`` the dtype's sublane tile (16 for
    bf16). Tokens are stably sorted by group first, so several tokens of one
    slot in one block (verify window, chunked prefill) and idle slots
    sharing the scratch block update a resident group one after another,
    never a stale copy of it.
    """
    _, _, kvp, bs, hd = pages.shape
    n = blk.size
    rows = math.gcd(bs, 32 // pages.dtype.itemsize)
    blk = blk.reshape(n).astype(jnp.int32)
    off = off.reshape(n).astype(jnp.int32)
    order = jnp.argsort(blk * (bs // rows) + off // rows,
                        stable=True).astype(jnp.int32)
    grp_spec = pl.BlockSpec(
        (1, 1, kvp, rows, hd),
        lambda i, lay, bl, of, od: (lay[0], bl[i], 0, of[i] // rows, 0))
    spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(n,),
        in_specs=[
            pl.BlockSpec((1, kvp, 1, hd),
                         lambda i, lay, bl, of, od: (od[i], 0, 0, 0)),
            grp_spec,
        ],
        out_specs=grp_spec,
    )
    return pl.pallas_call(
        _make_write_kernel(rows),
        grid_spec=spec,
        name="paged_kv_write",
        out_shape=jax.ShapeDtypeStruct(pages.shape, pages.dtype),
        input_output_aliases={5: 0},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=resolve_interpret(interpret),
    )(_layer_operand(layer), blk[order], off[order], order,
      jnp.pad(val.reshape(n, kvp, 1, val.shape[-1]).astype(pages.dtype),
              ((0, 0), (0, 0), (0, 0), (0, hd - val.shape[-1]))), pages)


def modeled_cache_bytes(nb: int, bs: int, kvp: int, hd: int,
                        itemsize: int) -> float:
    """HBM bytes ONE slot's attention reads per layer through this kernel:
    each owned K and V block streamed exactly once (the materialized
    ``paged_gather`` path pays this twice — once building the gathered
    copy, once reading it — plus the copy's write)."""
    return 2.0 * nb * bs * kvp * hd * itemsize
