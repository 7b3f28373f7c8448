"""Fused gather-up -> activation -> scatter-down decode FFN kernel.

The serving hot loop previously paid THREE passes per layer over the sparse
FFN: ``sparse_up_matmul`` for the gate pre-activation, (for GLU) a second
``sparse_up_matmul`` for the up-projection, then ``sparse_matmul_tokens``
for the down-projection — each materializing a (T, F)-shaped intermediate
in HBM between kernels. This kernel runs the whole per-tile chain in one
``pallas_call``: for every (token t, list slot i) grid point it DMAs ONLY
the tile-i weight columns/rows named by the token's packed tile list
(scalar prefetch — the DMA engine never touches a skipped tile), computes

    h_i = act(x_t @ Wg[:, tile_i]) [* (x_t @ Wu[:, tile_i])] [* mask_i]

entirely in VMEM/registers, and scatter-accumulates ``h_i @ Wd[tile_i, :]``
into the token's output row. HBM weight traffic per (token, layer) drops to
``nvalid x n_proj x tile x d_model x itemsize`` — exactly the paper's
"read only the live rows" claim, now with no intermediate round-trips.

Tile lists are the fixed-K padded int32 lists from
``predictors.pack_tile_indices`` (valid-first ascending, pads repeating the
row's first tile), which composes with PR 5's model-axis-local per-shard
packing unchanged. Numerics are pinned to the unfused pair: identical
per-tile dot shapes, identical f32 accumulation order over the same
ascending tile list — ``tests/test_fused_decode.py`` asserts bit-equality
against the ``sparse_up_matmul`` + ``sparse_matmul_tokens`` composition.

The kernel also emits the compact (T, K, tile) activation buffer so the
caller can reconstruct the full hidden activation (``scatter_compact``) for
the act/scores telemetry the γ-window machinery records — the scatter is
the same masked ``.at[].add`` the unfused path used, so duplicate pad tiles
contribute exactly once.

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


def _make_kernel(activation: str, shift: float, glu: bool, masked: bool):
    act_fn = acts.get(activation, shift=shift)

    def kernel(idx_ref, nvalid_ref, x_ref, *refs):
        refs = list(refs)
        wg_ref = refs.pop(0)          # gate projection (wu when not GLU)
        wu_ref = refs.pop(0) if glu else None
        wd_ref = refs.pop(0)
        m_ref = refs.pop(0) if masked else None
        y_ref, h_ref = refs
        t, i = pl.program_id(0), pl.program_id(1)

        @pl.when(i == 0)
        def _zero():
            y_ref[...] = jnp.zeros_like(y_ref)

        @pl.when(i < nvalid_ref[t])
        def _acc():
            x = x_ref[0]  # (1, d)
            h = act_fn(jax.lax.dot_general(
                x, wg_ref[...], (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32))
            if glu:
                h = h * jax.lax.dot_general(
                    x, wu_ref[...], (((1,), (0,)), ((), ())),
                    preferred_element_type=jnp.float32)
            if masked:
                h = h * m_ref[0]
            h_ref[0, 0] = h
            y_ref[0] += jax.lax.dot_general(
                h.astype(wd_ref.dtype), wd_ref[...], (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)

        @pl.when(i >= nvalid_ref[t])
        def _pad():  # padded slots: no DMA'd tile is used, block zeroed
            h_ref[...] = jnp.zeros_like(h_ref)

    return kernel


@functools.partial(jax.jit, static_argnames=("activation", "shift", "tile",
                                             "interpret"))
def fused_sparse_ffn(x, w_gate, wd, idx, nvalid, *, w_up=None, unit_mask=None,
                     activation: str = "relu", shift: float = 0.0,
                     tile: int = 128, interpret: Optional[bool] = None):
    """One-pass sparse FFN over per-token tile lists.

    x: (T, d); w_gate: (d, F) the activation-gated projection (``wu`` for a
    plain MLP, ``wg`` for GLU — pass the GLU's ``wu`` as ``w_up``); wd:
    (F, d_out); idx: (T, K) int32 tile ids (valid-first, in-range pads);
    nvalid: (T,) int32; unit_mask: optional (T, F) f32/bool unit-resolution
    mask multiplied into the hidden activation (the γ-window ``eff`` mask —
    a gathered tile may still have masked-off units inside it).

    Returns (y (T, d_out) f32, h_compact (T, K, tile) f32). ``y`` is the
    down-projection accumulated over the valid tiles in list order;
    ``h_compact[t, i]`` is tile ``idx[t, i]``'s hidden activation (zeros
    past nvalid) — scatter with ``scatter_compact`` to recover the (T, F)
    activation for telemetry. Rows with nvalid == 0 return exact zeros.
    """
    T, d = x.shape
    F = w_gate.shape[1]
    K = idx.shape[1]
    d_out = wd.shape[1]
    assert F % tile == 0 and wd.shape[0] == F
    glu = w_up is not None
    masked = unit_mask is not None

    # per-token operands carry a unit second-minor axis ((T, 1, d) with
    # block (1, 1, d)) so every block's last two dims either equal the
    # array's or are (8, 128)-aligned, as the TPU lowering requires
    tile_spec = pl.BlockSpec((d, tile), lambda t, i, idx, nv: (0, idx[t, i]))
    in_specs = [pl.BlockSpec((1, 1, d), lambda t, i, idx, nv: (t, 0, 0)),
                tile_spec]
    args = [x[:, None, :], w_gate]
    if glu:
        in_specs.append(tile_spec)
        args.append(w_up)
    in_specs.append(
        pl.BlockSpec((tile, d_out), lambda t, i, idx, nv: (idx[t, i], 0)))
    args.append(wd)
    if masked:
        in_specs.append(pl.BlockSpec(
            (1, 1, tile), lambda t, i, idx, nv: (t, 0, idx[t, i])))
        args.append(unit_mask.astype(jnp.float32)[:, None, :])

    spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(T, K),
        in_specs=in_specs,
        out_specs=[
            pl.BlockSpec((1, 1, d_out), lambda t, i, idx, nv: (t, 0, 0)),
            pl.BlockSpec((1, 1, 1, tile),
                         lambda t, i, idx, nv: (t, i, 0, 0)),
        ],
    )
    y, compact = pl.pallas_call(
        _make_kernel(activation, shift, glu, masked),
        grid_spec=spec,
        name="fused_sparse_ffn",
        out_shape=[
            jax.ShapeDtypeStruct((T, 1, d_out), jnp.float32),
            jax.ShapeDtypeStruct((T, K, 1, tile), jnp.float32),
        ],
        interpret=resolve_interpret(interpret),
    )(idx.astype(jnp.int32), nvalid.astype(jnp.int32), *args)
    return y[:, 0], compact[:, :, 0]


def scatter_compact(compact, idx, nvalid, n_tiles: int):
    """Place a (T, K, tile) compact activation buffer at its tile positions:
    returns (T, n_tiles * tile) f32 with exact zeros on non-gathered tiles.
    The same masked scatter-add ``sparse_up_matmul`` uses — padding is
    zeroed first, so duplicate pad indices contribute exactly once (i.e.
    nothing)."""
    T, K, tile = compact.shape
    valid = (jnp.arange(K)[None, :] < nvalid[:, None]).astype(compact.dtype)
    compact = compact * valid[:, :, None]
    y = jnp.zeros((T, n_tiles, tile), compact.dtype)
    y = y.at[jnp.arange(T)[:, None], idx].add(compact)
    return y.reshape(T, n_tiles * tile)


def modeled_weight_bytes(k_tiles: float, tile: int, d_model: int,
                         itemsize: int, n_proj: int) -> float:
    """Analytic HBM weight bytes ONE token reads through this kernel in one
    layer: ``k_tiles`` gathered tiles x ``n_proj`` projections touching that
    tile (gate + [up] + down) x the (tile x d_model) tile footprint. Derived
    purely from the BlockSpec geometry above — the roofline gate
    (launch/roofline.py) checks it against the engine's independently
    measured ``weight_io_bytes_per_step``."""
    return float(k_tiles) * n_proj * tile * d_model * itemsize
