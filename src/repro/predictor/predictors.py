"""Activation-sparsity predictors: know which FFN neurons fire BEFORE
paying for their weights (paper Sec. 5 headroom; SparseInfer 2411.12692,
ReLU^2-Wins 2402.03804).

Two predictor families, one contract. Given the FFN input x (the post-norm
block activation), a predictor produces a per-token *probe* approximating
the gate pre-activation ``x @ W_gate``; units whose probe exceeds the
layer's calibrated threshold are predicted to fire. Unit predictions are
rounded up to 128-lane tiles — the granularity the tile-gathered kernels
(kernels/sparse_matmul.py) read weights at — so a predicted mask is
directly a weight-I/O plan for BOTH the up- and down-projections.

* ``sign`` — training-free (SparseInfer-style): the probe is the sign-
  faithful low-precision product ``x @ W_lp`` where W_lp is the model's own
  gate weight cast to ``probe_dtype``. At probe_dtype == compute dtype the
  probe IS the pre-activation, so threshold = the activation's firing
  threshold gives recall 1.0 by construction (the exactness anchor).
* ``lowrank`` — learned: rank-r factors (A, B) distilled per layer from
  calibration activations (reduced-rank regression via SVD of the
  calibration pre-activations, predictor/calibration.py), probe =
  ``(x @ A) @ B`` — O(d*r + r*F) instead of O(d*F) probe flops.

Thresholds live per layer (``tau`` (L,)); calibration picks them to hit a
target recall. Everything is stacked on a leading layer axis so the
serving decode step scans over layers with no per-layer retracing.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, List, Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.core import activations as acts

PyTree = Any

TILE = 128  # default lane-width tile (the TPU DMA granularity)


def ffn_tile(cfg: ModelConfig) -> int:
    """The weight-gather tile width (models.common.ffn_gather_tile — the
    single source of truth shared with the serving decode steps). tile=1
    degenerates to the paper's exact row-skipping — useful on CPU-sized
    models where 128-wide tiles are never all-zero."""
    from repro.models.common import ffn_gather_tile
    return ffn_gather_tile(cfg)


@dataclasses.dataclass
class LayerReport:
    """Per-layer calibration metrics (predictor quality at the fitted tau)."""

    layer: int
    tau: float
    recall: float          # active units whose probe cleared tau
    tile_recall: float     # active units whose TILE was predicted (>= recall)
    precision: float       # predicted units that were truly active
    unit_density: float    # fraction of units predicted active
    tile_density: float    # fraction of 128-tiles predicted active (the I/O)


@dataclasses.dataclass
class Predictor:
    """A fitted predictor: stacked per-layer params + static serving knobs.

    params (leading axis = layer):
      sign:    {"w": (L, d, F) probe_dtype, "tau": (L,) f32}
      lowrank: {"a": (L, d, r), "b": (L, r, F), "tau": (L,) f32}

    ``k_tiles`` is the STATIC gather capacity per token: predicted tile
    lists are padded/truncated to exactly k_tiles indices so the jitted
    decode step never retraces (truncation is a recorded recall event).
    """

    kind: str  # "sign" | "lowrank"
    params: Dict[str, jnp.ndarray]
    n_tiles: int
    k_tiles: int
    tile: int = TILE
    target_recall: float = 1.0
    probe_dtype: str = "float32"
    reports: List[LayerReport] = dataclasses.field(default_factory=list)

    def layer_tau(self, layer: int) -> float:
        return float(self.params["tau"][layer])

    def mean_report(self) -> Dict[str, float]:
        if not self.reports:
            return {}
        keys = ("recall", "tile_recall", "precision", "unit_density",
                "tile_density")
        n = len(self.reports)
        return {k: sum(getattr(r, k) for r in self.reports) / n for k in keys}

    def describe(self) -> str:
        m = self.mean_report()
        extra = ("" if not m else
                 f" recall={m['recall']:.3f} tile_density="
                 f"{m['tile_density']:.3f}")
        return (f"{self.kind}-predictor(k_tiles={self.k_tiles}/"
                f"{self.n_tiles}, target_recall={self.target_recall})"
                + extra)


# ---------------------------------------------------------------------------
# in-graph probe + mask machinery (called from the jitted decode step)


def probe(kind: str, pred_l: PyTree, x: jnp.ndarray) -> jnp.ndarray:
    """Per-layer probe. x: (T, d) -> (T, F) f32 approximate pre-activation.

    kind is STATIC (bakes into the trace); pred_l is this layer's slice of
    the stacked predictor params.
    """
    if kind == "sign":
        w = pred_l["w"]
        return (x.astype(w.dtype) @ w).astype(jnp.float32)
    if kind == "lowrank":
        a, b = pred_l["a"], pred_l["b"]
        return ((x.astype(a.dtype) @ a) @ b).astype(jnp.float32)
    raise ValueError(f"unknown predictor kind {kind!r}")


def predict_units(kind: str, pred_l: PyTree, x: jnp.ndarray) -> jnp.ndarray:
    """(T, d) -> (T, F) bool predicted-active units (probe > layer tau)."""
    return probe(kind, pred_l, x) > pred_l["tau"].astype(jnp.float32)


def units_to_tiles(units: jnp.ndarray, tile: int = TILE) -> jnp.ndarray:
    """(T, F) unit mask -> (T, F // tile) tile mask (any unit in the tile)."""
    T, F = units.shape
    return jnp.any(units.reshape(T, F // tile, tile), axis=-1)


def tiles_to_units(tiles: jnp.ndarray, tile: int = TILE) -> jnp.ndarray:
    """(T, nT) tile mask -> (T, nT * tile) unit-resolution coverage mask."""
    return jnp.repeat(tiles, tile, axis=-1)


def pack_tile_indices(tile_mask: jnp.ndarray, k: int, n_groups: int = 1):
    """Fixed-capacity packing: (T, nT) bool -> (idx (T, k') int32,
    nvalid (T,) int32) with k' = k (n_groups rounds it up to a multiple).

    Active tiles come first (ascending tile id); padding repeats each row's
    first entry so every index stays in [0, nT) and padded DMAs revisit an
    already-fetched block. If a row has more than k active tiles the excess
    is dropped — a *recorded* recall event, never an out-of-range index.

    ``n_groups > 1`` makes the packing MODEL-AXIS-LOCAL for a TP-sharded
    FFN: the tile axis is cut into n_groups contiguous shard slices and
    each group selects (and truncates) its own ceil(k / n_groups) capacity
    from its local slice — so every shard's gather touches only tiles it
    owns, and truncation is load-balanced across shards instead of biased
    toward low tile ids. Because groups are contiguous ascending ranges,
    the valid-first flattened index list is still globally ascending: at
    full capacity (k == nT) the packed set — and the f32 accumulation
    order of the gathered matmuls — is identical to n_groups == 1, which
    is what keeps sharded-engine streams byte-identical to single-device.
    """
    T, nT = tile_mask.shape
    k = min(k, nT)
    if n_groups <= 1:
        # top_k on {0,1} scores is stable: equal scores keep ascending index
        # order, so actives (1.0) land first, each group id-ordered.
        _, idx = jax.lax.top_k(tile_mask.astype(jnp.float32), k)
        nvalid = jnp.minimum(jnp.sum(tile_mask.astype(jnp.int32), axis=-1),
                             k).astype(jnp.int32)
        pad = idx[:, :1]  # row's first selected tile (always in range)
        idx = jnp.where(jnp.arange(k)[None, :] < nvalid[:, None], idx, pad)
        return idx.astype(jnp.int32), nvalid
    if nT % n_groups:
        raise ValueError(f"n_tiles={nT} not divisible by "
                         f"n_groups={n_groups} shards")
    gsz = nT // n_groups
    k_g = min(gsz, -(-k // n_groups))
    mg = tile_mask.reshape(T, n_groups, gsz).astype(jnp.float32)
    _, idx_l = jax.lax.top_k(mg, k_g)  # (T, G, k_g) group-local, stable
    idx = idx_l + (jnp.arange(n_groups) * gsz)[None, :, None]  # global ids
    ng = jnp.minimum(jnp.sum(mg.astype(jnp.int32), axis=-1), k_g)  # (T, G)
    valid = jnp.arange(k_g)[None, None, :] < ng[:, :, None]
    # compact valid-first across groups (kernels expect actives, then pads);
    # stable top_k keeps group-major = globally ascending order
    kt = n_groups * k_g
    _, order = jax.lax.top_k(valid.reshape(T, kt).astype(jnp.float32), kt)
    idx = jnp.take_along_axis(idx.reshape(T, kt), order, axis=-1)
    nvalid = jnp.sum(ng, axis=-1).astype(jnp.int32)
    pad = idx[:, :1]
    idx = jnp.where(jnp.arange(kt)[None, :] < nvalid[:, None], idx, pad)
    return idx.astype(jnp.int32), nvalid


def covered_tiles(idx: jnp.ndarray, nvalid: jnp.ndarray,
                  n_tiles: int) -> jnp.ndarray:
    """Invert packing: which tiles will actually be gathered. (T, k), (T,)
    -> (T, n_tiles) bool. Differs from the input mask only when packing
    truncated (more actives than k). Compares over a (T, k, n_tiles)
    index grid: no scatter."""
    k = idx.shape[1]
    valid = jnp.arange(k)[None, :] < nvalid[:, None]
    tiles = jnp.arange(n_tiles, dtype=idx.dtype)
    return jnp.any((idx[:, :, None] == tiles) & valid[:, :, None], axis=1)


# ---------------------------------------------------------------------------
# training-free construction


def gate_weight_key(cfg: ModelConfig) -> str:
    """The FFN weight whose pre-activation the activation gates: the gate
    projection for GLU FFNs, the single up projection otherwise."""
    return "wg" if cfg.ffn_kind == "glu" else "wu"


def firing_threshold(cfg: ModelConfig) -> float:
    thr = acts.firing_threshold(cfg.activation, cfg.sparsity.shift)
    if thr is None:
        raise ValueError(
            f"activation {cfg.activation!r} has no exact firing threshold; "
            "the predictor subsystem needs a ReLU-family activation "
            "(relu / shifted_relu / fatrelu)")
    return thr


def sign_predictor(params, cfg: ModelConfig, *,
                   probe_dtype: str = "bfloat16",
                   tau: Optional[float] = None,
                   tile: Optional[int] = None,
                   k_tiles: Optional[int] = None) -> Predictor:
    """Training-free sign predictor straight from the model weights — no
    calibration pass. tau defaults to the activation's firing threshold
    (exact at probe_dtype == compute dtype; calibrate for margin at lower
    probe precision)."""
    thr = firing_threshold(cfg)
    w = params["layers"]["ffn"][gate_weight_key(cfg)]
    L = w.shape[0]
    tile = ffn_tile(cfg) if tile is None else tile
    if cfg.d_ff % tile:
        raise ValueError(f"d_ff={cfg.d_ff} is not a multiple of tile={tile}")
    n_tiles = cfg.d_ff // tile
    tau = thr if tau is None else float(tau)
    return Predictor(
        kind="sign",
        params={"w": w.astype(jnp.dtype(probe_dtype)),
                "tau": jnp.full((L,), tau, jnp.float32)},
        n_tiles=n_tiles,
        k_tiles=n_tiles if k_tiles is None else min(k_tiles, n_tiles),
        tile=tile,
        target_recall=1.0,
        probe_dtype=probe_dtype,
    )
