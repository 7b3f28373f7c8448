"""Per-kernel microbenchmarks for the fused decode hot loop (ISSUE 7).

Three lowerings of the same sparse decode FFN are timed against each other —
dense XLA matmuls, the unfused Pallas pair (``sparse_up_matmul`` +
``sparse_matmul_tokens``), and the one-pass fused kernel
(``fused_sparse_ffn``) — plus the paged-attention pair (materializing
``paged_gather`` + dense softmax vs the in-kernel block-table gather).
Each row reports wall time AND the analytic HBM bytes the lowering moves,
so the bytes column shows the point of the exercise even on CPU (where the
Pallas kernels run in interpret mode and wall time is meaningless — on an
accelerator the same rows time the compiled kernels).

The module also runs the serving bytes-per-step roofline
(``launch/roofline.py``) and emits its modeled/measured agreement as
``kernel_bytes_ratio`` — the trajectory headline the CI bench gate bounds
to [0.85, 1.15] (benchmarks/check_trajectory.py): if the kernel BlockSpec
geometry and the engine's density accounting drift apart, the gate trips
even though every stream still matches.
"""
from __future__ import annotations

import json
import os
import time

import jax
import jax.numpy as jnp
import numpy as np

SMOKE = bool(int(os.environ.get("BENCH_SMOKE", "0")))


def _time(fn, iters=None):
    iters = iters or (3 if SMOKE else 20)
    fn()  # compile / warm
    t0 = time.time()
    for _ in range(iters):
        out = fn()
    jax.block_until_ready(out)
    return (time.time() - t0) / iters * 1e6


def _ffn_case():
    """One decode-step FFN workload at 50% tile density (GLU, f32)."""
    from repro.predictor.predictors import pack_tile_indices

    T, d, F, tile = (4, 64, 512, 128) if SMOKE else (8, 128, 1024, 128)
    n_tiles = F // tile
    rng = np.random.RandomState(0)
    x = jnp.asarray(rng.randn(T, d), jnp.float32)
    wg = jnp.asarray(rng.randn(d, F) / np.sqrt(d), jnp.float32)
    wu = jnp.asarray(rng.randn(d, F) / np.sqrt(d), jnp.float32)
    wd = jnp.asarray(rng.randn(F, d) / np.sqrt(F), jnp.float32)
    mask = jnp.asarray(rng.rand(T, n_tiles) < 0.5) | jnp.eye(
        T, n_tiles, dtype=bool)[:, :n_tiles]
    idx, nvalid = pack_tile_indices(mask, n_tiles)
    return x, wg, wu, wd, idx, nvalid, tile, n_tiles


def _ffn_rows():
    from repro.kernels import fused_decode as kfd
    from repro.kernels import sparse_matmul as ksm

    x, wg, wu, wd, idx, nvalid, tile, n_tiles = _ffn_case()
    T, d = x.shape
    F = wg.shape[1]
    itemsize = 4
    k_mean = float(jnp.mean(nvalid))
    dense_bytes = 3 * d * F * itemsize
    sparse_bytes = kfd.modeled_weight_bytes(k_mean, tile, d, itemsize, 3)

    def dense():
        h = jnp.maximum(x @ wg, 0.0) * (x @ wu)
        return h @ wd

    def unfused():
        pre = ksm.sparse_up_matmul(x, wg, idx, nvalid, tile=tile)
        hh = jnp.maximum(pre, 0.0) * ksm.sparse_up_matmul(x, wu, idx,
                                                          nvalid, tile=tile)
        return ksm.sparse_matmul_tokens(hh, wd, idx, nvalid, tile=tile)

    def fused():
        y, _ = kfd.fused_sparse_ffn(x, wg, wd, idx, nvalid, w_up=wu,
                                    activation="relu", tile=tile)
        return y

    # fused == unfused bit-exactly (the exactness tests pin this; assert
    # here too so a bench run can never report a speedup of wrong numerics)
    np.testing.assert_array_equal(np.asarray(fused()), np.asarray(unfused()))
    rows, full = [], {}
    for name, fn, nbytes in (("dense_xla", dense, dense_bytes),
                             ("unfused_pair", unfused, sparse_bytes),
                             ("fused_kernel", fused, sparse_bytes)):
        us = _time(fn)
        rows.append(f"kernel/ffn_{name},{us:.0f},weight_bytes={nbytes:.0f}")
        full[f"ffn_{name}"] = {"us_per_call": us, "weight_bytes": nbytes}
    full["ffn_density"] = k_mean / n_tiles
    return rows, full


def _attn_rows():
    from repro.kernels import paged_attention as kpa
    from repro.models import common as cm

    b, W, kvp, g, hd = (2, 1, 2, 2, 16) if SMOKE else (4, 1, 4, 2, 32)
    n_blocks, bs, nb = (9, 8, 4) if SMOKE else (17, 16, 8)
    rng = np.random.RandomState(1)
    q = jnp.asarray(rng.randn(b, W, kvp, g, hd), jnp.float32)
    kp = jnp.asarray(rng.randn(1, n_blocks, kvp, bs, hd), jnp.float32)
    vp = jnp.asarray(rng.randn(1, n_blocks, kvp, bs, hd), jnp.float32)
    table = jnp.asarray(rng.randint(1, n_blocks, (b, nb)), jnp.int32)
    pos = jnp.full((b, W), nb * bs - 1, jnp.int32)
    itemsize = 4
    cache = kpa.modeled_cache_bytes(nb, bs, kvp, hd, itemsize) * b

    def gathered():
        kg = cm.paged_gather(kp[0], table)
        vg = cm.paged_gather(vp[0], table)
        return cm.window_attention(q, kg, vg, pos, window=0)

    def fused():
        return kpa.paged_window_attention(q, kp, vp, table, pos, 0, window=0)

    np.testing.assert_allclose(np.asarray(fused()), np.asarray(gathered()),
                               atol=1e-5)
    rows, full = [], {}
    # the gather path writes AND re-reads the materialized copy on top of
    # the one pool read the kernel pays
    for name, fn, nbytes in (("gathered_xla", gathered, 3 * cache),
                             ("fused_kernel", fused, cache)):
        us = _time(fn)
        rows.append(f"kernel/attn_{name},{us:.0f},cache_bytes={nbytes:.0f}")
        full[f"attn_{name}"] = {"us_per_call": us, "cache_bytes": nbytes}
    return rows, full


def run():
    rows, full = [], {}
    r, f = _ffn_rows()
    rows += r
    full.update(f)
    r, f = _attn_rows()
    rows += r
    full.update(f)

    # serving bytes-per-step roofline: kernel-modeled vs engine-measured
    from repro.launch.roofline import serving_records

    recs = serving_records("tiny-relu")
    ratios = [rec["ratio"] for rec in recs]
    ratio = float(np.mean(ratios))
    full["kernel_bytes_ratio"] = ratio
    full["roofline"] = recs
    rows.append(f"kernel/bytes_ratio,0,modeled_over_measured={ratio:.4f}")

    os.makedirs("experiments", exist_ok=True)
    with open("experiments/bench_kernels.json", "w") as f:
        json.dump(full, f, indent=2)
    return rows


if __name__ == "__main__":
    for r in run():
        print(r)
