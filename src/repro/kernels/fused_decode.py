"""Fused gather-up -> activation -> scatter-down decode FFN kernel.

The serving hot loop previously paid THREE passes per layer over the sparse
FFN: ``sparse_up_matmul`` for the gate pre-activation, (for GLU) a second
``sparse_up_matmul`` for the up-projection, then ``sparse_matmul_tokens``
for the down-projection — each materializing a (T, F)-shaped intermediate
in HBM between kernels. This kernel runs the whole per-tile chain in one
``pallas_call``, tile-major: the wrapper forms the UNION of the tiles the
batch's per-token lists name (one ascending list, scalar prefetch), and
for every union tile the grid DMAs its weight columns/rows ONCE — the DMA
engine never touches a tile no token named — and computes, for all T
tokens at once,

    h = act(x @ Wg[:, tile]) [* (x @ Wu[:, tile])] * m[:, tile]

entirely in VMEM/registers, then accumulates ``h @ Wd[tile, :]`` into the
resident (T, d_out) output. ``m`` is each token's membership of the tile
(1 where the token named it, 0 where it did not), times the optional
unit mask. HBM weight traffic per layer drops to ``|union| x n_proj x tile
x d_model x itemsize``: a tile that every token names is read once per
call, not once per token, and never more than a per-token kernel would
read for any lists (the two agree at T = 1). The arithmetic intensity of
a fetched tile is T FLOP/byte, far under the chip's ridge for any decode
batch, so the rows a mask zeroes cost no measurable time.

Tile lists are the fixed-K padded int32 lists from
``predictors.pack_tile_indices`` (valid-first ascending, pads repeating the
row's first tile), which composes with PR 5's model-axis-local per-shard
packing unchanged. Numerics are pinned to the unfused pair: token t's
output is the sum over the union in ascending order, and the tiles t did
not name add exact zeros, so each row equals the per-token sum over t's
own tiles in the same order — ``tests/test_fused_decode.py`` asserts
bit-equality against the ``sparse_up_matmul`` + ``sparse_matmul_tokens``
composition.

The kernel also returns the full (T, F) hidden activation for the
act/scores telemetry and the γ-window mask refresh: each union tile's
(T, tile) block is written in place, and every other tile keeps the zeros
of the buffer it is aliased to, so h is exactly zero outside each token's
tiles.

MoE (documented XLA fallback): this fused kernel has no expert-offset
variant yet, so MoE serving (models/moe.py) keeps its grouped one-hot
dispatch einsums — the frozen-exactness XLA path — and the engine forces
``fast_kernels=False`` for MoE configs with a warning. The building blocks
for a future fused expert path already exist as standalone kernels
(``sparse_matmul.expert_up_matmul`` / ``expert_down_matmul`` over
``expert_tile_lists``): fusing them here is a matter of adding the
expert-major index_map split (idx // tpe, idx % tpe) to the weight
BlockSpecs, exactly as those kernels do.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core import activations as acts
from repro.kernels.runtime import resolve_interpret


def _make_kernel(activation: str, shift: float, glu: bool):
    act_fn = acts.get(activation, shift=shift)

    def kernel(uidx_ref, meta_ref, x_ref, *refs):
        refs = list(refs)
        wg_ref = refs.pop(0)          # gate projection (wu when not GLU)
        wu_ref = refs.pop(0) if glu else None
        wd_ref, m_ref, _h_init, y_ref, h_ref = refs
        i, n_union = pl.program_id(0), meta_ref[0]

        @pl.when(i == 0)
        def _zero():
            y_ref[...] = jnp.zeros_like(y_ref)

        @pl.when(i < n_union)
        def _acc():
            x = x_ref[...]  # (T, d)
            h = act_fn(jax.lax.dot_general(
                x, wg_ref[...], (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32))
            if glu:
                h = h * jax.lax.dot_general(
                    x, wu_ref[...], (((1,), (0,)), ((), ())),
                    preferred_element_type=jnp.float32)
            h = h * m_ref[...]
            h_ref[...] = h
            y_ref[...] += jax.lax.dot_general(
                h.astype(wd_ref.dtype), wd_ref[...], (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)

        # an empty union visits only pad steps: the block they hold is
        # written back all the same, so it must hold zeros
        @pl.when((i == 0) & (n_union == 0))
        def _empty():
            h_ref[...] = jnp.zeros_like(h_ref)

    return kernel


def union_tiles(idx, nvalid, n_tiles: int):
    """The batch union of per-token tile lists, as the kernel walks it.

    idx: (T, K) int32 tile ids (valid-first, in-range pads); nvalid: (T,).
    Returns (member (T, n_tiles) bool — token t named tile j —, uidx
    (n_tiles,) int32 the union's tiles ascending, padded with the LAST
    union tile so pad grid steps issue no new DMA, n_union () int32).
    Compares only — no scatter, no sort."""
    from repro.predictor.predictors import covered_tiles

    member = covered_tiles(idx, nvalid, n_tiles)
    tiles = jnp.arange(n_tiles, dtype=jnp.int32)
    union = jnp.any(member, axis=0)
    below = jnp.cumsum(union.astype(jnp.int32))  # union tiles <= j
    n_union = below[-1]
    # the k-th union tile is the count of tiles with at most k union
    # tiles at or below them; past the union that count is n_tiles
    kth = jnp.sum(below[None, :] <= tiles[:, None], axis=1, dtype=jnp.int32)
    last = jnp.max(jnp.where(union, tiles, 0))
    return member, jnp.minimum(kth, last), n_union


def tile_counts(idx, nvalid, n_tiles: int):
    """(tiles each token names (T,) int32, tiles one call fetches () int32):
    the (token, tile) pairs and the union the kernel reads once each."""
    member, _, n_union = union_tiles(idx, nvalid, n_tiles)
    return jnp.sum(member, axis=1, dtype=jnp.int32), n_union


@functools.partial(jax.jit, static_argnames=("activation", "shift", "tile",
                                             "interpret"))
def fused_sparse_ffn(x, w_gate, wd, idx, nvalid, *, w_up=None, unit_mask=None,
                     layer=None, activation: str = "relu", shift: float = 0.0,
                     tile: int = 128, interpret: Optional[bool] = None):
    """One-pass sparse FFN over per-token tile lists, one DMA per union tile.

    x: (T, d); w_gate: (d, F) the activation-gated projection (``wu`` for a
    plain MLP, ``wg`` for GLU — pass the GLU's ``wu`` as ``w_up``); wd:
    (F, d_out); idx: (T, K) int32 tile ids (valid-first, in-range pads);
    nvalid: (T,) int32; unit_mask: optional (T, F) f32/bool unit-resolution
    mask multiplied into the hidden activation (the γ-window ``eff`` mask —
    a gathered tile may still have masked-off units inside it).

    With ``layer`` (a scalar, may be traced) the weights are the layer
    scan's whole stacks — (L, d, F) and (L, F, d_out) — and the kernel
    DMAs its tiles of that layer straight from them, so no layer's weights
    are sliced out and copied first.

    Returns (y (T, d_out) f32, h (T, F) f32). ``y`` is each token's
    down-projection accumulated over its valid tiles in ascending order;
    ``h`` is the hidden activation, exact zeros outside each token's valid
    tiles. Rows with nvalid == 0 return exact zeros."""
    if layer is None:
        w_gate, wd, layer = w_gate[None], wd[None], 0
        w_up = None if w_up is None else w_up[None]
    T, d = x.shape
    F = w_gate.shape[2]
    n_tiles = F // tile
    d_out = wd.shape[2]
    assert F % tile == 0 and wd.shape[1] == F
    glu = w_up is not None

    member, uidx, n_union = union_tiles(idx, nvalid, n_tiles)
    m = jnp.repeat(member.astype(jnp.float32), tile, axis=1)  # (T, F)
    if unit_mask is not None:
        m = m * unit_mask.astype(jnp.float32)

    # blocks span all T tokens: their last two dims are the array's own
    # (T) or a 128-lane tile, as the TPU lowering requires
    tile_spec = pl.BlockSpec((None, d, tile),
                             lambda i, u, mt: (mt[1], 0, u[i]))
    unit_spec = pl.BlockSpec((T, tile), lambda i, u, mt: (0, u[i]))
    in_specs = [pl.BlockSpec((T, d), lambda i, u, mt: (0, 0)), tile_spec]
    args = [x, w_gate]
    if glu:
        in_specs.append(tile_spec)
        args.append(w_up)
    in_specs += [pl.BlockSpec((None, tile, d_out),
                              lambda i, u, mt: (mt[1], u[i], 0)),
                 unit_spec,
                 pl.BlockSpec(memory_space=pl.ANY)]  # h's zeros, in place
    args += [wd, m, jnp.zeros((T, F), jnp.float32)]

    spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(n_tiles,),
        in_specs=in_specs,
        out_specs=[pl.BlockSpec((T, d_out), lambda i, u, mt: (0, 0)),
                   unit_spec],
    )
    return pl.pallas_call(
        _make_kernel(activation, shift, glu),
        grid_spec=spec,
        name="fused_sparse_ffn",
        out_shape=[jax.ShapeDtypeStruct((T, d_out), jnp.float32),
                   jax.ShapeDtypeStruct((T, F), jnp.float32)],
        # operand index counts the two scalar-prefetch operands
        input_output_aliases={2 + len(args) - 1: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=resolve_interpret(interpret),
    )(uidx, jnp.stack([n_union, jnp.asarray(layer, jnp.int32)]), *args)


def modeled_weight_bytes(k_tiles: float, tile: int, d_model: int,
                         itemsize: int, n_proj: int) -> float:
    """Analytic HBM weight bytes ONE call of this kernel reads in one
    layer: ``k_tiles`` union tiles (each read once, whatever the number of
    tokens that name it) x ``n_proj`` projections touching that tile (gate
    + [up] + down) x the (tile x d_model) tile footprint. Derived purely
    from the BlockSpec geometry above — for a call of one token the union
    is its own list, which the roofline gate (launch/roofline.py) checks
    against the engine's independently measured
    ``weight_io_bytes_per_step``."""
    return float(k_tiles) * n_proj * tile * d_model * itemsize
