"""Pallas TPU kernels: tile-gathered sparse matmuls (the paper's row-skipping,
TPU-native — DESIGN.md §3).

``y = x @ w`` computed only over selected F-tiles. Tile index lists arrive
via *scalar prefetch*, so the weight BlockSpec's ``index_map`` dereferences
``idx[...]`` — the DMA engine fetches ONLY the active weight tiles from HBM.
This is exactly the paper's "skip loading zero rows" (App. B Fig. 9a)
expressed in the TPU memory hierarchy: HBM→VMEM traffic and MXU work both
shrink by the sparsity factor.

Dense-family variants:

* ``sparse_matmul`` — one shared tile list for all T rows (the batch-union
  selection the γ-window down-projection uses). Grid = (D_tiles, K) with K
  innermost: the (T, Dt) output block stays resident in VMEM while the K
  gathered tiles accumulate into it.
* ``sparse_matmul_tokens`` — PER-ROW tile lists (idx (T, K), nvalid (T,)):
  every row gathers its own tiles. This is the continuous-batching shape —
  co-scheduled requests predict different active sets and must not union
  (predictor serving mode, serving/engine.py).
* ``sparse_up_matmul`` — gathers OUTPUT tiles (columns of w): only the
  predicted-active up-projection tiles are computed/read; the rest of the
  output is exactly zero. The kernel emits a compact (T, K, tile) buffer
  (every grid point writes its own block, so nothing is left
  uninitialized); a plain-XLA scatter-add places it, padding masked to
  zero so duplicate pad indices are harmless.

Grouped per-expert gathers (MoE serving, models/moe.py): expert top-k
routing is the same structure one level up — a token reads only its routed
experts' weight tiles. ``expert_tile_lists`` flattens each token's top-k
expert ids into a per-token GLOBAL tile list over the (E, F) expert-unit
grid (expert e owns tiles [e·tpe, (e+1)·tpe)), and ``expert_up_matmul`` /
``expert_down_matmul`` are the stacked-weight (E, d, F) / (E, F, d)
twins of ``sparse_up_matmul`` / ``sparse_matmul_tokens``: the BlockSpec
index_map splits a global tile id into (expert, within-expert tile), so the
DMA engine fetches only activated experts' tiles. Router sparsity and
γ-window/ReLU sparsity thus ride the same gather mechanism — compose them
by intersecting the expert tile list with the within-expert active tiles.

``interpret=None`` (the default) autodetects: interpret mode on CPU (this
container), compiled on TPU. Pass an explicit bool to override.

Tensor-parallel serving (engine ``mesh=``): the weight's F axis is sharded
over the "model" mesh axis, and the caller packs the tile lists
MODEL-AXIS-LOCALLY (predictors.pack_tile_indices ``n_groups=TP`` — each
shard's indices name only tiles in its own F slice, capacity balanced per
shard), so every gather a shard issues is against weight tiles it already
owns: no cross-shard weight traffic, and per-device HBM reads shrink by
sparsity x 1/TP. The kernels themselves are unchanged — index locality is
a property of the lists they are handed.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.runtime import resolve_interpret as _resolve_interpret


def _kernel(idx_ref, nvalid_ref, x_ref, w_ref, o_ref):
    j, i = pl.program_id(0), pl.program_id(1)

    @pl.when(i == 0)
    def _zero():
        o_ref[...] = jnp.zeros_like(o_ref)

    @pl.when(i < nvalid_ref[0])
    def _acc():
        o_ref[...] += jax.lax.dot_general(
            x_ref[...], w_ref[...], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)


@functools.partial(jax.jit, static_argnames=("tile", "block_d", "interpret"))
def sparse_matmul(x, w, idx, nvalid, *, tile: int = 128, block_d: int = 256,
                  interpret=None):
    """x: (T, F), w: (F, D), idx: (K,) int32 tile ids, nvalid: () int32.

    Returns (T, D) f32. One tile list shared by every row (batch-union
    selection). interpret=None autodetects from the backend.
    """
    T, F = x.shape
    D = w.shape[1]
    K = idx.shape[0]
    block_d = min(block_d, D)
    assert F % tile == 0 and D % block_d == 0

    grid = (D // block_d, K)
    spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=grid,
        in_specs=[
            pl.BlockSpec((T, tile), lambda j, i, idx, nv: (0, idx[i])),
            pl.BlockSpec((tile, block_d), lambda j, i, idx, nv: (idx[i], j)),
        ],
        out_specs=pl.BlockSpec((T, block_d), lambda j, i, idx, nv: (0, j)),
    )
    return pl.pallas_call(
        _kernel,
        grid_spec=spec,
        out_shape=jax.ShapeDtypeStruct((T, D), jnp.float32),
        interpret=_resolve_interpret(interpret),
    )(idx, jnp.reshape(nvalid, (1,)).astype(jnp.int32), x, w)


def _kernel_tokens(idx_ref, nvalid_ref, x_ref, w_ref, o_ref):
    t, i = pl.program_id(0), pl.program_id(2)

    @pl.when(i == 0)
    def _zero():
        o_ref[...] = jnp.zeros_like(o_ref)

    @pl.when(i < nvalid_ref[t])
    def _acc():
        o_ref[0] += jax.lax.dot_general(
            x_ref[0], w_ref[...], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)


@functools.partial(jax.jit, static_argnames=("tile", "block_d", "interpret"))
def sparse_matmul_tokens(x, w, idx, nvalid, *, tile: int = 128,
                         block_d: int = 256, interpret=None):
    """Per-row tile gather: x (T, F), w (F, D), idx (T, K) int32 tile ids,
    nvalid (T,) int32 valid-count per row. Returns (T, D) f32.

    Row t accumulates only its own idx[t, :nvalid[t]] tiles — the
    continuous-batching predictor shape, where each slot's predicted active
    set differs. Pad idx[t, i >= nvalid[t]] with any in-range tile id
    (repeating a valid id keeps the padded DMAs cache-resident); padded
    iterations are skipped by the nvalid guard either way.
    """
    T, F = x.shape
    D = w.shape[1]
    K = idx.shape[1]
    block_d = min(block_d, D)
    assert F % tile == 0 and D % block_d == 0

    # rows ride a unit second-minor axis ((T, 1, F), block (1, 1, tile)):
    # the TPU lowering needs each block's last two dims aligned or whole
    grid = (T, D // block_d, K)
    spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, tile),
                         lambda t, j, i, idx, nv: (t, 0, idx[t, i])),
            pl.BlockSpec((tile, block_d),
                         lambda t, j, i, idx, nv: (idx[t, i], j)),
        ],
        out_specs=pl.BlockSpec((1, 1, block_d),
                               lambda t, j, i, idx, nv: (t, 0, j)),
    )
    return pl.pallas_call(
        _kernel_tokens,
        grid_spec=spec,
        name="sparse_matmul_tokens",
        out_shape=jax.ShapeDtypeStruct((T, 1, D), jnp.float32),
        interpret=_resolve_interpret(interpret),
    )(idx.astype(jnp.int32), nvalid.astype(jnp.int32), x[:, None, :], w)[:, 0]


def _kernel_up(idx_ref, nvalid_ref, x_ref, w_ref, o_ref):
    t, i = pl.program_id(0), pl.program_id(1)

    @pl.when(i < nvalid_ref[t])
    def _compute():
        o_ref[0, 0] = jax.lax.dot_general(
            x_ref[0], w_ref[...], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(i >= nvalid_ref[t])
    def _zero():  # padded iterations: no MXU work, block zeroed
        o_ref[...] = jnp.zeros_like(o_ref)


@functools.partial(jax.jit, static_argnames=("tile", "interpret"))
def sparse_up_matmul(x, w, idx, nvalid, *, tile: int = 128, interpret=None):
    """Output-tile gather for the up-projection: x (T, d), w (d, F),
    idx (T, K) int32 OUTPUT tile ids, nvalid (T,). Returns (T, F) f32 where
    only row t's selected output tiles are computed (their weight columns
    read); everything else is exactly 0.

    The kernel writes a compact (T, K, tile) buffer — each grid point owns
    its own output block, so no block is left unvisited/uninitialized;
    iterations past nvalid[t] skip the matmul and just zero their block
    (their idx entries repeat the row's first tile, so their weight
    prefetch revisits an already-fetched block). A scatter-ADD then places
    the tiles, with padding masked to zero so duplicate pad indices cannot
    clobber real tiles.
    """
    T, d = x.shape
    F = w.shape[1]
    K = idx.shape[1]
    assert F % tile == 0
    n_tiles = F // tile

    # unit second-minor axes ((T, 1, d) in, (T, K, 1, tile) out) keep every
    # block's last two dims whole or aligned for the TPU lowering
    grid = (T, K)
    spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, d), lambda t, i, idx, nv: (t, 0, 0)),
            pl.BlockSpec((d, tile), lambda t, i, idx, nv: (0, idx[t, i])),
        ],
        out_specs=pl.BlockSpec((1, 1, 1, tile),
                               lambda t, i, idx, nv: (t, i, 0, 0)),
    )
    compact = pl.pallas_call(
        _kernel_up,
        grid_spec=spec,
        name="sparse_up_matmul",
        out_shape=jax.ShapeDtypeStruct((T, K, 1, tile), jnp.float32),
        interpret=_resolve_interpret(interpret),
    )(idx.astype(jnp.int32), nvalid.astype(jnp.int32), x[:, None, :], w)
    compact = compact[:, :, 0]
    valid = (jnp.arange(K)[None, :] < nvalid[:, None]).astype(jnp.float32)
    compact = compact * valid[:, :, None]
    y = jnp.zeros((T, n_tiles, tile), jnp.float32)
    y = y.at[jnp.arange(T)[:, None], idx].add(compact)
    return y.reshape(T, F)


# ---------------------------------------------------------------------------
# grouped per-expert gathers (MoE serving)


def expert_tile_lists(topi, tiles_per_expert: int, k_valid=None):
    """Per-token GLOBAL tile lists from top-k expert routing.

    topi: (T, k) int32 expert ids; tiles_per_expert = F // tile. Token t's
    list is its k experts' contiguous tile ranges in routing-priority order:
    [topi[t, 0]·tpe .. topi[t, 0]·tpe + tpe − 1, topi[t, 1]·tpe .. ] —
    exactly the blocks ``expert_up_matmul``/``expert_down_matmul`` gather
    from the stacked (E, ...) expert weights.

    k_valid: optional (T,) int32 count of live expert assignments per token
    (tokens that lost capacity slots route fewer); entries past
    k_valid·tpe repeat the token's FIRST tile so padded ids stay in range
    (the kernels skip them via nvalid either way). Returns
    (idx (T, k·tpe) int32, nvalid (T,) int32)."""
    T, k = topi.shape
    tpe = tiles_per_expert
    idx = (topi.astype(jnp.int32)[:, :, None] * tpe
           + jnp.arange(tpe, dtype=jnp.int32)[None, None, :])
    idx = idx.reshape(T, k * tpe)
    if k_valid is None:
        return idx, jnp.full((T,), k * tpe, jnp.int32)
    nvalid = (k_valid.astype(jnp.int32) * tpe)
    pos = jnp.arange(k * tpe, dtype=jnp.int32)[None, :]
    idx = jnp.where(pos < nvalid[:, None], idx, idx[:, :1])
    return idx, nvalid


def _kernel_expert_up(idx_ref, nvalid_ref, x_ref, w_ref, o_ref):
    t, i = pl.program_id(0), pl.program_id(1)

    @pl.when(i < nvalid_ref[t])
    def _compute():
        o_ref[...] = jax.lax.dot_general(
            x_ref[...], w_ref[0], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)[None]

    @pl.when(i >= nvalid_ref[t])
    def _zero():
        o_ref[...] = jnp.zeros_like(o_ref)


@functools.partial(jax.jit, static_argnames=("tile", "interpret"))
def expert_up_matmul(x, w, idx, nvalid, *, tile: int = 128, interpret=None):
    """Expert-offset up-projection gather: x (T, d), w (E, d, F) stacked
    expert weights, idx (T, K) GLOBAL tile ids over the (E, F) grid
    (``expert_tile_lists``), nvalid (T,). Returns the compact (T, K, tile)
    f32 hidden blocks — token t's block i is x[t] @ w[e, :, ft·tile:...]
    with (e, ft) = divmod(idx[t, i], F // tile); blocks past nvalid[t] are
    exactly 0. Only routed experts' weight columns are DMA'd.

    Stays compact (no scatter): the natural consumer is the activation +
    ``expert_down_matmul``, which reads the same (idx, nvalid) layout."""
    T, d = x.shape
    E, _, F = w.shape
    K = idx.shape[1]
    assert F % tile == 0
    tpe = F // tile

    grid = (T, K)
    spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, d), lambda t, i, idx, nv: (t, 0)),
            pl.BlockSpec((1, d, tile),
                         lambda t, i, idx, nv: (idx[t, i] // tpe, 0,
                                                idx[t, i] % tpe)),
        ],
        out_specs=pl.BlockSpec((1, 1, tile), lambda t, i, idx, nv: (t, i, 0)),
    )
    return pl.pallas_call(
        _kernel_expert_up,
        grid_spec=spec,
        out_shape=jax.ShapeDtypeStruct((T, K, tile), jnp.float32),
        interpret=_resolve_interpret(interpret),
    )(idx.astype(jnp.int32), nvalid.astype(jnp.int32), x, w)


def _kernel_expert_down(idx_ref, nvalid_ref, c_ref, w_ref, o_ref):
    t, i = pl.program_id(0), pl.program_id(2)

    @pl.when(i == 0)
    def _zero():
        o_ref[...] = jnp.zeros_like(o_ref)

    @pl.when(i < nvalid_ref[t])
    def _acc():
        o_ref[...] += jax.lax.dot_general(
            c_ref[0], w_ref[0], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)


@functools.partial(jax.jit, static_argnames=("block_d", "interpret"))
def expert_down_matmul(compact, w, idx, nvalid, *, block_d: int = 256,
                       interpret=None):
    """Expert-offset down-projection: compact (T, K, tile) hidden blocks
    (``expert_up_matmul`` layout, post-activation), w (E, F, d) stacked
    expert weights, idx/nvalid as in ``expert_up_matmul``. Returns (T, d)
    f32: token t accumulates block i @ w[e, ft·tile:..., :] over its
    nvalid[t] live blocks — only routed experts' weight rows are DMA'd.

    NOTE: accumulates raw block products; the caller folds each token's
    combine gate into its blocks (scale compact per expert) beforehand."""
    T, K, tile = compact.shape
    E, F, d = w.shape
    assert F % tile == 0
    tpe = F // tile
    block_d = min(block_d, d)
    assert d % block_d == 0

    grid = (T, d // block_d, K)
    spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, tile), lambda t, j, i, idx, nv: (t, i, 0)),
            pl.BlockSpec((1, tile, block_d),
                         lambda t, j, i, idx, nv: (idx[t, i] // tpe,
                                                   idx[t, i] % tpe, j)),
        ],
        out_specs=pl.BlockSpec((1, block_d),
                               lambda t, j, i, idx, nv: (t, j)),
    )
    return pl.pallas_call(
        _kernel_expert_down,
        grid_spec=spec,
        out_shape=jax.ShapeDtypeStruct((T, d), jnp.float32),
        interpret=_resolve_interpret(interpret),
    )(idx.astype(jnp.int32), nvalid.astype(jnp.int32), compact, w)
