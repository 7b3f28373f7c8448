"""Shared model components.

Everything here is pure-jnp, mesh-agnostic (sharding is applied from the
outside via logical-axis rules), and eval_shape-friendly (init allocates only
through jax.random so the dry-run can stay on ShapeDtypeStructs).

TPU adaptation notes (see DESIGN.md §3):
  * attention for long sequences is an online-softmax chunked loop (flash
    attention algorithmically, pure XLA);
  * decode attention runs over a KV cache whose *sequence* axis may be sharded
    (GSPMD inserts the partial-softmax all-reduces — flash-decode for free);
  * the paper's row-skipping sparse matmul becomes *tile*-gathered matmul with
    static top-k capacity (`select_active_tiles` + `gathered_matmul`).

Head padding (probe: jit rejects uneven shardings, so the q-head axis must be
a multiple of the model-axis size 16). We use a *per-group padded layout*:
for GQA with H q-heads and K kv-heads, real group size r = H/K is padded to
g = Hp/K slots per kv group (Hp = round_up(H, 16); K | Hp holds for every
assigned arch since K is a power of two or equals H). Padded slots hold zero
weights and are masked after attention, so the math is exactly GQA. MHA archs
(K == H) pad K alongside H with zero K/V heads. Grouped attention einsums
then need no gather maps at all.
"""
from __future__ import annotations

import math
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig

PyTree = Any

# ---------------------------------------------------------------------------
# init helpers


def dense_init(rng, shape, in_axis_size, dtype):
    scale = 1.0 / math.sqrt(max(1, in_axis_size))
    return (jax.random.normal(rng, shape, jnp.float32) * scale).astype(dtype)


def embed_init(rng, shape, dtype):
    return (jax.random.normal(rng, shape, jnp.float32) * 0.02).astype(dtype)


# ---------------------------------------------------------------------------
# normalization


def init_norm(cfg: ModelConfig, dim: int, dtype) -> PyTree:
    p = {"scale": jnp.ones((dim,), dtype)}
    if cfg.norm_kind == "layernorm":
        p["bias"] = jnp.zeros((dim,), dtype)
    return p


def apply_norm(p: PyTree, x: jnp.ndarray, cfg: ModelConfig, eps: float = 1e-6) -> jnp.ndarray:
    xf = x.astype(jnp.float32)
    if cfg.norm_kind == "layernorm":
        mu = jnp.mean(xf, axis=-1, keepdims=True)
        var = jnp.mean(jnp.square(xf - mu), axis=-1, keepdims=True)
        y = (xf - mu) * jax.lax.rsqrt(var + eps)
        y = y * p["scale"].astype(jnp.float32) + p["bias"].astype(jnp.float32)
    else:  # rmsnorm
        var = jnp.mean(jnp.square(xf), axis=-1, keepdims=True)
        y = xf * jax.lax.rsqrt(var + eps) * p["scale"].astype(jnp.float32)
    return y.astype(x.dtype)


def rms_norm_headdim(scale: jnp.ndarray, x: jnp.ndarray, eps: float = 1e-6) -> jnp.ndarray:
    """qk-norm (qwen3): RMS-normalize the head_dim axis."""
    xf = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(xf), axis=-1, keepdims=True)
    return (xf * jax.lax.rsqrt(var + eps) * scale.astype(jnp.float32)).astype(x.dtype)


# ---------------------------------------------------------------------------
# RoPE


def rope_freqs(head_dim: int, theta: float) -> np.ndarray:
    return 1.0 / (theta ** (np.arange(0, head_dim, 2, dtype=np.float32) / head_dim))


def apply_rope(x: jnp.ndarray, positions: jnp.ndarray, theta: float) -> jnp.ndarray:
    """x: (..., seq, heads, head_dim); positions: (..., seq) broadcastable."""
    hd = x.shape[-1]
    freqs = jnp.asarray(rope_freqs(hd, theta))  # (hd/2,)
    angles = positions[..., None].astype(jnp.float32) * freqs  # (..., seq, hd/2)
    angles = angles[..., None, :]  # broadcast over heads
    cos, sin = jnp.cos(angles), jnp.sin(angles)
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# padding geometry (DESIGN.md §4)

TP = 16  # model-axis size of the production mesh
VOCAB_MULTIPLE = 2048  # 16 shards x 128 lanes


def round_up(x: int, m: int) -> int:
    return ((x + m - 1) // m) * m


class HeadGeometry:
    """Padded GQA/MHA layout for an arch (see module docstring)."""

    def __init__(self, n_heads: int, n_kv: int, head_dim: int, tp: int = TP):
        self.n_heads = n_heads
        self.head_dim = head_dim
        if n_kv == n_heads:  # MHA: pad kv alongside q
            self.hp = round_up(n_heads, tp)
            self.kvp = self.hp
            self.group = 1
            self.real_per_group = 1  # slot j==0 real iff kv head real
        else:
            self.hp = round_up(n_heads, tp)
            assert self.hp % n_kv == 0, (n_heads, n_kv)
            self.kvp = n_kv
            self.group = self.hp // n_kv
            self.real_per_group = n_heads // n_kv
        self.n_kv = n_kv

    def q_slot_mask(self) -> np.ndarray:
        """(hp,) 1.0 for real q-head slots in the per-group padded layout."""
        if self.group == 1:
            m = (np.arange(self.hp) < self.n_heads)
        else:
            j = np.arange(self.hp) % self.group
            m = j < self.real_per_group
        return m.astype(np.float32)

    def kv_slot_mask(self) -> np.ndarray:
        return (np.arange(self.kvp) < self.n_kv).astype(np.float32)

    def scatter_q(self, w_real: jnp.ndarray, axis: int) -> jnp.ndarray:
        """Place a real-head-indexed array into the padded layout (init only)."""
        shape = list(w_real.shape)
        shape[axis] = self.hp
        out = jnp.zeros(shape, w_real.dtype)
        if self.group == 1:
            return jax.lax.dynamic_update_slice_in_dim(out, w_real, 0, axis)
        # real head h = k*r + j  ->  padded slot k*g + j
        idx = (np.arange(self.n_heads) // self.real_per_group) * self.group + (
            np.arange(self.n_heads) % self.real_per_group)
        return out.at[tuple(slice(None) if a != axis else idx
                            for a in range(len(shape)))].set(w_real)


def padded_vocab(vocab: int) -> int:
    return round_up(vocab, VOCAB_MULTIPLE)


def vocab_logit_mask(vocab: int, vocab_p: int) -> jnp.ndarray:
    return jnp.where(jnp.arange(vocab_p) < vocab, 0.0, -1e9).astype(jnp.float32)


def _largest_divisor_leq(n: int, cap: int) -> int:
    for c in range(min(cap, n), 0, -1):
        if n % c == 0:
            return c
    return 1


# ---------------------------------------------------------------------------
# attention (train/prefill): online-softmax chunked == flash-attention in XLA


def flash_attention(
    q: jnp.ndarray,  # (b, s, kvp, g, d) per-group padded layout
    k: jnp.ndarray,  # (b, skv, kvp, d)
    v: jnp.ndarray,  # (b, skv, kvp, d)
    causal: bool = True,
    window: int = 0,  # sliding-window size; 0 = global
    q_chunk: int = 512,
    kv_chunk: int = 512,
    q_offset: int = 0,  # absolute position of q[0]
) -> jnp.ndarray:
    """Exact attention with O(s·chunk) memory and ~causal FLOPs.

    Outer python loop over q chunks (static slices); inner lax.scan over only
    the kv chunks a q chunk can see (causal / sliding window), with an online
    softmax (m, l, acc) carry in f32. Returns (b, s, kvp, g, d).
    """
    b, s, kvp, g, d = q.shape
    skv = k.shape[1]
    q_chunk = _largest_divisor_leq(s, min(q_chunk, s))
    kv_chunk = _largest_divisor_leq(skv, min(kv_chunk, skv))
    n_q = s // q_chunk
    scale = 1.0 / math.sqrt(d)

    outs = []
    for i in range(n_q):
        q0 = i * q_chunk
        cq = q_chunk
        # keep operands in compute dtype; accumulate in f32 via
        # preferred_element_type (avoids materializing f32 copies of q/k/v)
        qi = jax.lax.slice_in_dim(q, q0, q0 + cq, axis=1) * jnp.asarray(scale, q.dtype)
        q_pos = q0 + q_offset + jnp.arange(cq)

        # kv chunk range this q chunk can see (static, aligned bounds)
        hi = min(skv, q0 + q_offset + cq) if causal else skv
        lo = max(0, q0 + q_offset - window + 1) if window else 0
        lo = (lo // kv_chunk) * kv_chunk
        hi = min(skv, round_up(max(hi, lo + 1), kv_chunk))
        n_kv = (hi - lo) // kv_chunk

        base = lo + jnp.arange(n_kv, dtype=jnp.int32) * kv_chunk

        def body(carry, b0):
            # slice the kv chunk inside the body (no stacked operand copies)
            m, l, acc = carry
            kj = jax.lax.dynamic_slice_in_dim(k, b0, kv_chunk, axis=1)
            vj = jax.lax.dynamic_slice_in_dim(v, b0, kv_chunk, axis=1)
            logits = jnp.einsum("bqhgd,bkhd->bhgqk", qi, kj,
                                preferred_element_type=jnp.float32)
            kpos = b0 + jnp.arange(kv_chunk)
            mask = jnp.ones((cq, kv_chunk), bool)
            if causal:
                mask &= kpos[None, :] <= q_pos[:, None]
            if window:
                mask &= kpos[None, :] > q_pos[:, None] - window
            logits = jnp.where(mask[None, None, None], logits, -1e30)
            mj = jnp.maximum(m, jnp.max(logits, axis=-1))
            p = jnp.exp(logits - mj[..., None])
            corr = jnp.exp(m - mj)
            l = l * corr + jnp.sum(p, axis=-1)
            acc = acc * corr[..., None] + jnp.einsum(
                "bhgqk,bkhd->bhgqd", p.astype(q.dtype), vj,
                preferred_element_type=jnp.float32)
            return (mj, l, acc), None

        m0 = jnp.full((b, kvp, g, cq), -1e30, jnp.float32)
        l0 = jnp.zeros((b, kvp, g, cq), jnp.float32)
        a0 = jnp.zeros((b, kvp, g, cq, d), jnp.float32)
        (m, l, acc), _ = jax.lax.scan(body, (m0, l0, a0), base)
        o = acc / jnp.maximum(l, 1e-30)[..., None]
        outs.append(o.transpose(0, 3, 1, 2, 4))  # (b, cq, kvp, g, d)
    return jnp.concatenate(outs, axis=1).astype(q.dtype)


def window_attention(
    q: jnp.ndarray,  # (b, W, kvp, g, d) a W-token window per sequence
    k_cache: jnp.ndarray,  # (b, kvp, S, d) HEAD-MAJOR layout
    v_cache: jnp.ndarray,
    pos: jnp.ndarray,  # (b, W) absolute position of each window token
    window: int = 0,
) -> jnp.ndarray:
    """Grouped attention for a W-token window over a (possibly seq-sharded)
    cache, causal WITHIN the window: query i attends to cache positions
    <= pos[:, i] (each window token's K/V is already written at its own
    position, so the window verifies in one pass — the speculative-decoding
    target forward). W == 1 is exactly single-token decode attention.

    The cache is head-major (b, kvp, S, d): both einsums consume it with
    (b, h) as batch dims and contract d / S directly — no transposed copies
    of the cache are ever materialized (this layout change removed ~2/3 of
    decode cache traffic, EXPERIMENTS.md §Perf).

    softmax reductions over the cache S axis are GSPMD-partitionable, so when
    the cache is sharded on S over the `model` axis this lowers to the
    flash-decode pattern (local partial max/sum + all-reduce) automatically.
    """
    b, W, kvp, g, d = q.shape
    S = k_cache.shape[2]
    scale = 1.0 / math.sqrt(d)
    qs = (q * jnp.asarray(scale, q.dtype)).astype(k_cache.dtype)
    logits = jnp.einsum("bqhgd,bhsd->bhgqs", qs, k_cache,
                        preferred_element_type=jnp.float32)
    valid = jnp.arange(S)[None, None, :] <= pos[:, :, None]  # (b, W, S)
    if window:
        valid &= jnp.arange(S)[None, None, :] > pos[:, :, None] - window
    logits = jnp.where(valid[:, None, None], logits, -1e30)
    w = jax.nn.softmax(logits, axis=-1)
    # batch dims lead the output and the window axis moves after the dot:
    # XLA:CPU has no kernel for a bf16 x bf16 -> f32 dot whose output puts a
    # free dim (q) ahead of the batch dim h ("bhgqs,bhsd->bqhgd" raised
    # "Unsupported element type for DotThunk" at g > 1)
    out = jnp.einsum("bhgqs,bhsd->bhgqd", w.astype(k_cache.dtype), v_cache,
                     preferred_element_type=jnp.float32)
    return out.transpose(0, 3, 1, 2, 4).astype(q.dtype)


def decode_attention(
    q: jnp.ndarray,  # (b, kvp, g, d) one new token per sequence
    k_cache: jnp.ndarray,  # (b, kvp, S, d) HEAD-MAJOR layout
    v_cache: jnp.ndarray,
    pos: jnp.ndarray,  # (b,) index of the current (just-written) token
    window: int = 0,
) -> jnp.ndarray:
    """Single-step grouped attention — ``window_attention`` at W = 1."""
    return window_attention(q[:, None], k_cache, v_cache, pos[:, None],
                            window)[:, 0]


# ---------------------------------------------------------------------------
# paged (block-table) KV cache — continuous-batching serving
#
# A shared pool of fixed-size blocks (L, n_blocks, kvp, block_size, hd) holds
# the K/V of every in-flight request; each request owns an ordered list of
# block ids (its "block table" row). Sequences of different lengths coexist
# without padding the pool to max_len: a request only holds the blocks its
# current length needs, and retirement returns them to the allocator.
# Block 0 is reserved as a scratch block: idle batch slots and block-table
# padding point at it, so writes from inactive slots land harmlessly there.

SCRATCH_BLOCK = 0


def init_paged_cache(cfg: ModelConfig, n_blocks: int, block_size: int,
                     dtype=None, sharding=None,
                     head_dim: Optional[int] = None) -> PyTree:
    """Block pool, head-major within a block (decode reads it untransposed).

    ``sharding`` (an optional jax Sharding, e.g. NamedSharding over the
    serving mesh from rules.paged_cache_pspec) allocates the pool directly
    into its distributed layout — a production pool is sized to fill HBM
    across the mesh and must never materialize on one device first. Still
    mesh-agnostic: the layout decision lives with the caller.

    ``head_dim`` (None = the model's) stores each K/V row that wide, the
    model's values first and zeros after: the Pallas kernels' pool keeps
    whole 128-lane rows (kernels/paged_attention.py ``pool_head_dim``)."""
    g = HeadGeometry(cfg.n_heads, cfg.n_kv_heads, cfg.resolved_head_dim)
    dtype = dtype or jnp.dtype(cfg.compute_dtype)
    shape = (cfg.n_layers, n_blocks, g.kvp, block_size,
             head_dim or g.head_dim)
    kw = {} if sharding is None else {"device": sharding}
    return {"k": jnp.zeros(shape, dtype, **kw),
            "v": jnp.zeros(shape, dtype, **kw)}


def paged_gather(pages_l: jnp.ndarray, table: jnp.ndarray) -> jnp.ndarray:
    """Assemble per-request contiguous K or V views from the pool.

    pages_l: (n_blocks, kvp, bs, hd) one layer's pool; table: (b, nb) block
    ids in sequence order. Returns (b, kvp, nb*bs, hd) — the head-major
    layout decode_attention consumes. Positions past a request's length hold
    stale/scratch data and must be masked by `pos` (decode_attention does).
    """
    b, nb = table.shape
    _, kvp, bs, hd = pages_l.shape
    gath = pages_l[table]  # (b, nb, kvp, bs, hd)
    return gath.transpose(0, 2, 1, 3, 4).reshape(b, kvp, nb * bs, hd)


def paged_write_window(pages: jnp.ndarray, layer, table: jnp.ndarray,
                       pos: jnp.ndarray, val: jnp.ndarray, block_size: int,
                       enable: Optional[jnp.ndarray] = None, *,
                       fast_kernels: bool = False) -> jnp.ndarray:
    """Scatter a W-token window's K or V per request through the block table.

    pages: (L, n_blocks, kvp, bs, hd), the whole stacked pool; layer: scalar
    (may be traced); table: (b, nb); pos: (b, W) absolute write positions;
    val: (b, W, kvp, hd); enable: (b, W) bool — window tokens past a slot's
    valid window length (and every token of an idle slot) are routed to the
    scratch block, so a write can NEVER land outside the blocks a request
    owns. A true scatter — no full-layer rewrite rides the loop. Shared by
    the speculative verify window AND chunked prefill (transformer.py
    ``prefill_chunk_paged``), whose chunks resume at arbitrary block-
    aligned positions over possibly prefix-cache-shared tables.

    ``fast_kernels`` stores the rows with the aliased Pallas writer
    (kernels/paged_attention.py ``paged_kv_write``) instead of XLA's
    scatter: the same values at the same places (rows zero-padded to the
    pool's width), written where they lie, without the relayout of the
    whole pool that the scatter's preferred layout forces around a
    program whose kernels read row-major blocks."""
    nb = table.shape[1]
    blk = jnp.take_along_axis(table, jnp.clip(pos // block_size, 0, nb - 1),
                              axis=1)  # (b, W)
    if enable is not None:
        blk = jnp.where(enable, blk, SCRATCH_BLOCK)
    off = pos % block_size
    if fast_kernels:
        from repro.kernels import paged_attention as kpa
        return kpa.paged_kv_write(pages, val, layer, blk, off)
    return pages.at[layer, blk, :, off, :].set(val.astype(pages.dtype))


def paged_write_token(pages: jnp.ndarray, layer, table: jnp.ndarray,
                      pos: jnp.ndarray, val: jnp.ndarray,
                      block_size: int, *,
                      fast_kernels: bool = False) -> jnp.ndarray:
    """Scatter one token's K or V per request — ``paged_write_window`` at
    W = 1. pos: (b,); val: (b, kvp, hd)."""
    return paged_write_window(pages, layer, table, pos[:, None],
                              val[:, None], block_size,
                              fast_kernels=fast_kernels)


def paged_write_prefill(pages: jnp.ndarray, kv: jnp.ndarray,
                        blocks: jnp.ndarray, block_size: int) -> jnp.ndarray:
    """Write a whole prompt's K or V into freshly allocated blocks.

    pages: (L, n_blocks, kvp, bs, hd'); kv: (L, s, kvp, hd) from prefill,
    hd <= hd' (rows zero-padded to the pool's width); blocks: (nb,) with
    nb*bs >= s (tail zero-padded inside the last block).
    """
    L, s, kvp, _ = kv.shape
    hd = pages.shape[-1]
    nb = blocks.shape[0]
    pad = nb * block_size - s
    kv = jnp.pad(kv, ((0, 0), (0, pad), (0, 0), (0, hd - kv.shape[-1])))
    tiles = kv.reshape(L, nb, block_size, kvp, hd).transpose(0, 1, 3, 2, 4)
    return pages.at[:, blocks].set(tiles.astype(pages.dtype))


# ---------------------------------------------------------------------------
# the paper's mechanism: tile-level activation sparsity (DESIGN.md §3)


def tile_scores(h: jnp.ndarray, tile: int) -> jnp.ndarray:
    """Per-tile activity score. h: (..., F) -> (..., F//tile)."""
    F = h.shape[-1]
    ht = jnp.abs(h).reshape(h.shape[:-1] + (F // tile, tile))
    return jnp.max(ht, axis=-1)


def select_active_tiles(
    scores: jnp.ndarray,  # (tokens, n_tiles) or (n_tiles,)
    density: float,
    n_groups: int = 1,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Static-capacity top-k tile selection, batch-union, group-balanced.

    Returns (idx, mask): idx (k_total,) int32 *global* tile indices, mask
    (k_total,) {0,1} marking tiles that were truly active (score > 0). Groups
    keep the selection balanced across TP shards so the gather stays
    shard-local when the weight's F axis is sharded n_groups-way.
    """
    if scores.ndim == 2:  # union over tokens (batch aggregated sparsity)
        scores = jnp.max(scores, axis=0)
    n_tiles = scores.shape[-1]
    gsz = n_tiles // n_groups
    k_g = max(1, int(math.ceil(density * gsz)))
    sg = scores.reshape(n_groups, gsz)
    top, idx_l = jax.lax.top_k(sg, k_g)  # (g, k_g) group-local indices
    idx = idx_l + (jnp.arange(n_groups) * gsz)[:, None]
    mask = (top > 0).astype(scores.dtype)
    return idx.reshape(-1).astype(jnp.int32), mask.reshape(-1)


def gathered_matmul(
    x: jnp.ndarray,  # (tokens, F) sparse-ish input
    w: jnp.ndarray,  # (F, D) weights
    idx: jnp.ndarray,  # (k,) active tile indices
    mask: jnp.ndarray,  # (k,) validity
    tile: int,
) -> jnp.ndarray:
    """y = x @ w computed only over the selected F tiles (XLA path).

    This is the paper's "skip zero rows" on TPU: only k·tile rows of w are
    read and multiplied. The Pallas kernel (kernels/sparse_matmul.py) is the
    deployment version; this gather+dot is mathematically identical and is
    what the dry-run lowers (cost_analysis reflects the FLOP/byte savings).
    """
    t, F = x.shape
    D = w.shape[1]
    k = idx.shape[0]
    xt = x.reshape(t, F // tile, tile)
    xg = jnp.take(xt, idx, axis=1) * mask[None, :, None].astype(x.dtype)
    wt = w.reshape(F // tile, tile, D)
    wg = jnp.take(wt, idx, axis=0)
    return jax.lax.dot_general(
        xg.reshape(t, k * tile), wg.reshape(k * tile, D),
        (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32,
    ).astype(x.dtype)


def pick_group_tile(F: int, n_groups: int) -> int:
    """Largest tile size dividing F/n_groups, sublane-aligned (%8), with at
    least ~4 tiles per group for useful top-k granularity."""
    per = F // n_groups
    cap = max(64, per // 4)
    for t in range(cap, 7, -1):
        if per % t == 0 and t % 8 == 0:
            return t
    for t in range(cap, 0, -1):
        if per % t == 0:
            return t
    return per


def ffn_gather_tile(cfg: ModelConfig) -> int:
    """The FFN weight-gather tile width: cfg.sparsity.tile_size when it
    divides d_ff (default 128 = TPU lane width), else the aligned fallback.
    The single source of truth for the granularity shared by the serving
    decode steps' tile-activity scores (models/transformer.py) and the
    activity predictors' masks (repro.predictor) — they must agree or
    predicted masks stop being weight-I/O plans."""
    ts = cfg.sparsity.tile_size
    return ts if cfg.d_ff % ts == 0 else pick_group_tile(cfg.d_ff, 1)


def grouped_sparse_matmul(x, w, density: float, n_groups: int):
    """Shard-local tile-gathered matmul (the §Perf optimization).

    The F axis is cut into `n_groups` groups aligned with the weight's
    sharding (n_groups = TP degree makes every gather shard-local: indices
    and weight slices live on the same chip, so GSPMD emits NO weight
    all-gather — only the usual small TP psum of the (t, D) output).
    Capacity is balanced per group, which also load-balances the TP shards.
    """
    t, F = x.shape
    D = w.shape[1]
    tile = pick_group_tile(F, n_groups)
    per = F // n_groups
    tiles_g = per // tile
    k_g = max(1, int(math.ceil(density * tiles_g)))

    xt = x.reshape(t, n_groups, tiles_g, tile)
    sc = jnp.max(jnp.abs(xt), axis=(0, 3))  # (G, tiles_g) union over tokens
    top, idx = jax.lax.top_k(sc, k_g)  # (G, k_g) group-local tile ids
    mask = (top > 0).astype(x.dtype)

    xg = jnp.take_along_axis(xt, idx[None, :, :, None], axis=2)  # (t,G,k,c)
    xg = xg * mask[None, :, :, None]
    w4 = w.reshape(n_groups, tiles_g, tile, D)
    wg = jnp.take_along_axis(w4, idx[:, :, None, None], axis=1)  # (G,k,c,D)
    return jnp.einsum("tgkc,gkcd->td", xg, wg,
                      preferred_element_type=jnp.float32).astype(x.dtype)


def maybe_sparse_matmul(x, w, cfg: ModelConfig, density: float,
                        n_groups: int = 0):
    """Dense x@w, or tile-gathered if a sparse decode path is configured."""
    if density >= 1.0:
        return x @ w
    n_groups = n_groups or cfg.sparsity.n_groups
    if n_groups > 1 and x.shape[1] % n_groups == 0:
        return grouped_sparse_matmul(x, w, density, n_groups)
    sc = tile_scores(x, cfg.sparsity.tile_size)
    idx, mask = select_active_tiles(sc, density, 1)
    return gathered_matmul(x, w, idx, mask, cfg.sparsity.tile_size)


# ---------------------------------------------------------------------------
# sparsity instrumentation (paper Figs. 1/2/4; Table 1 sparsity columns)


def site_sparsity(x: jnp.ndarray) -> jnp.ndarray:
    return jnp.mean((x == 0).astype(jnp.float32))


def wrap_block(policy: str, block_fn):
    """Wrap a family block fn with the configured remat policy.

    "save_ars" saves the TP-collective outputs (attn_out / ffn_out) so the
    backward pass re-runs neither those matmuls nor their all-reduces —
    trades a little activation memory for ~1/3 of the TP collective volume
    (the §Perf lever for collective-bound training).
    """
    if policy in (None, "none"):
        return block_fn
    if policy == "save_ars":
        pol = jax.checkpoint_policies.save_only_these_names(
            "attn_out", "ffn_out")
    else:
        pol = (None if policy == "full"
               else jax.checkpoint_policies.dots_with_no_batch_dims_saveable)

    def block(p, x, cfg, *, positions, stats, return_kv=False):
        assert not return_kv

        def inner(p_, x_, cfg_):
            return block_fn(p_, x_, cfg_, positions=positions, stats=stats)
        kw = {} if pol is None else {"policy": pol}
        return jax.checkpoint(inner, static_argnums=(2,), **kw)(p, x, cfg)

    return block


def cast_params(params: PyTree, cfg: ModelConfig) -> PyTree:
    """Mixed precision: cast f32 master params to the compute dtype at the
    model entry point (differentiable; grads accumulate back in f32)."""
    cdt = jnp.dtype(cfg.compute_dtype)
    return jax.tree.map(
        lambda p: p.astype(cdt) if p.dtype == jnp.float32 else p, params)


class StatsCollector:
    """Accumulates per-site sparsity/preactivation stats during apply().

    Inactive (the default) it is free: `add` becomes a no-op so the dry-run
    HLO contains no instrumentation.
    """

    def __init__(self, active: bool = False, raw: bool = False):
        self.active = active
        self.raw = active and raw
        self.stats: Dict[str, jnp.ndarray] = {}

    def add(self, name: str, value: jnp.ndarray):
        if self.active:
            self.stats[name] = value

    def add_raw(self, name: str, x: jnp.ndarray):
        """Capture a full activation tensor (calibration runs only — e.g.
        the predictor harness needs per-layer FFN inputs, not summaries).
        No-op unless the collector was built with raw=True."""
        if self.raw:
            self.stats[name] = jax.lax.stop_gradient(x)

    def add_sparsity(self, name: str, x: jnp.ndarray):
        if self.active:
            self.stats[name] = site_sparsity(jax.lax.stop_gradient(x))

    def add_preact(self, name: str, x: jnp.ndarray):
        if self.active:
            xf = jax.lax.stop_gradient(x).astype(jnp.float32)
            self.stats[name + "/mean"] = jnp.mean(xf)
            self.stats[name + "/std"] = jnp.std(xf)
            self.stats[name + "/frac_neg"] = jnp.mean((xf < 0).astype(jnp.float32))
