"""Roofline reports: dry-run tables AND the serving bytes-per-step gate.

Two entry points:

  python -m repro.launch.roofline experiments/dryrun_results.jsonl [--md]
      The original dry-run table (per-cell three-term roofline, bottleneck,
      MODEL_FLOPS ratio, memory fit) over launch/dryrun.py JSONL records.

  python -m repro.launch.roofline --serving [--check [--tol 0.15]]
      The SERVING decode roofline: runs the tiny continuous-batching engine
      with the fused Pallas kernels forced on (interpret mode on CPU) in
      autoregressive and predictor modes, and reports, per mode, three
      independent figures for FFN weight HBM bytes per decode step:

        measured — the engine's own density-accounted
                   ``weight_io_bytes_per_step()`` (telemetry recorded
                   in-graph while serving real requests);
        modeled  — the fused kernel's BlockSpec geometry
                   (``fused_decode.modeled_weight_bytes``: union tiles x
                   projections x tile footprint) for a call of one token,
                   whose union is its own tile list, at the measured
                   density;
        hlo      — trip-count-scaled down-projection dot reads counted in
                   the FROZEN XLA decode step's compiled HLO
                   (``hlo_cost.CostModel.dot_weight_bytes``), the
                   ground-truth anchor for what a dense step reads.

      --check turns the report into a CI regression gate: modeled/measured
      must agree within --tol (default 15%), and the HLO count must match
      the engine's dense accounting — exits nonzero on violation
      (.github/workflows/ci.yml bench-smoke).

Hardware model (v5e per chip): 197 TFLOP/s bf16, 819 GB/s HBM, ~50 GB/s ICI.
Terms (per chip, per step):
  compute    = HLO_FLOPs / peak          (trip-count-corrected, hlo_cost.py)
  memory     = HLO_bytes / HBM_bw        (post-fusion op traffic, bf16-scaled)
  collective = wire_bytes / ICI_bw       (ring multipliers, loop-aware)
"""
from __future__ import annotations

import argparse
import json
import sys
from typing import Dict, List

HBM_GB = 16.0
HBM_BW = 819e9  # v5e HBM bytes/s


def load(path: str) -> List[Dict]:
    out = []
    with open(path) as f:
        for line in f:
            line = line.strip()
            if line:
                out.append(json.loads(line))
    return out


def fmt_t(x: float) -> str:
    if x >= 1.0:
        return f"{x:8.2f}s "
    if x >= 1e-3:
        return f"{x * 1e3:8.2f}ms"
    return f"{x * 1e6:8.1f}us"


def table(recs: List[Dict], md: bool = False) -> str:
    rows = []
    hdr = ("cell", "sparse", "t_compute", "t_memory", "t_coll", "bound",
           "MF/HLO", "peak_GB", "fit", "step_est")
    rows.append(hdr)
    for r in recs:
        if "error" in r:
            rows.append((f"{r['arch']}/{r['shape']}", str(r.get("sparse", 0)),
                         "ERROR", "", "", "", "", "", "", ""))
            continue
        peak = r["peak_bytes_per_chip"] / 1e9
        cell = f"{r['arch']}/{r['shape']}"
        step = max(r["t_compute"], r["t_memory"], r["t_collective"])
        rows.append((
            cell, str(r.get("sparse", 0) or "-"),
            fmt_t(r["t_compute"]), fmt_t(r["t_memory"]),
            fmt_t(r["t_collective"]), r["bottleneck"][:4],
            f"{r.get('useful_flops_ratio', 0):.2f}",
            f"{peak:.1f}", "Y" if peak <= HBM_GB else "OVER",
            fmt_t(step),
        ))
    widths = [max(len(str(row[i])) for row in rows) for i in range(len(hdr))]
    sep = " | " if md else "  "
    lines = []
    for j, row in enumerate(rows):
        line = sep.join(str(c).ljust(w) for c, w in zip(row, widths))
        lines.append(("| " + line + " |") if md else line)
        if md and j == 0:
            lines.append("|" + "|".join("-" * (w + 2) for w in widths) + "|")
    return "\n".join(lines)


# ---------------------------------------------------------------------------
# serving bytes-per-step roofline (fused-kernel gate)


def kernel_bytes_per_step(engine) -> float:
    """Per-device FFN weight HBM bytes one token's decode step reads
    through the fused kernel path, modeled PURELY from the kernel's
    BlockSpec geometry (``fused_decode.modeled_weight_bytes``) at the
    engine's measured density: a one-token call's union is its own list,
    so mean gathered tiles/step x projections touching each tile x the
    (tile x d_model) tile footprint x layers, split by the FFN's effective
    TP. A batched call reads the union of its tokens' lists once, at most
    this figure times the tokens. Independent of the engine's own
    accounting — the gate compares the two per-token figures."""
    import jax.numpy as jnp

    from repro.kernels import fused_decode as kfd
    from repro.models import common as cm

    cfg = engine.cfg
    itemsize = jnp.dtype(cfg.compute_dtype).itemsize
    n_proj = 3 if cfg.ffn_kind == "glu" else 2
    if engine.predictor is not None:
        tile, n_tiles = engine.predictor.tile, engine.predictor.n_tiles
    else:
        tile = cm.ffn_gather_tile(cfg)
        n_tiles = cfg.d_ff // tile
    dens = (1.0 if not engine._dens_n
            else engine._dens_sum / engine._dens_n)
    per_layer = kfd.modeled_weight_bytes(dens * n_tiles, tile, cfg.d_model,
                                         itemsize, n_proj)
    return cfg.n_layers * per_layer / engine.ffn_tp


def hlo_decode_ffn_bytes(engine, n_proj: int = 1) -> float:
    """Down-projection weight bytes a compiled FROZEN decode step reads,
    counted in its optimized HLO: trip-count-scaled dots whose RHS is the
    (d_ff, d_model) down-projection weight (``CostModel.dot_weight_bytes``
    — the layer scan's while trip count multiplies the single textual dot
    by n_layers). ``n_proj`` scales the one counted projection to the
    engine mode's skippable scope (the up/gate dots have a transposed
    shape, so the (d_ff, d_model) count is unambiguous)."""
    import jax.numpy as jnp

    from repro.launch.hlo_cost import CostModel

    cfg = engine.cfg
    n = engine.scheduler.n_slots
    nb = engine.scheduler.max_blocks_per_seq
    zi = jnp.zeros((n,), jnp.int32)
    args = (engine.params, engine.pages,
            jnp.zeros((n, nb), jnp.int32), zi, zi, engine.masks,
            jnp.ones((n,), bool), jnp.zeros((n,), jnp.float32), zi,
            jnp.zeros((n,), jnp.float32), jnp.zeros((n, 2), jnp.uint32), zi)
    text = engine._decode.lower(*args).compile().as_text()
    cm_ = CostModel(text)
    # the down-projection is a plain matmul; einsum-labeled dots (op_name
    # carries the spec, e.g. the attention output projection "bshd,hde->")
    # can collide with its (d_ff, d_model) weight shape and are excluded
    return n_proj * cm_.dot_weight_bytes((cfg.d_ff, cfg.d_model),
                                         exclude_re="->")


def serving_records(name: str = "tiny-relu", max_new: int = 8) -> List[Dict]:
    """Serve a few requests through the tiny engine with fast kernels
    forced on (interpret mode on CPU), in autoregressive and predictor
    modes; return one record per mode with the three bytes-per-step
    figures (measured / modeled / hlo) and the v5e memory-roofline time."""
    import jax
    import numpy as np

    from repro.configs import get_config
    from repro.models import registry
    from repro.predictor import calibrate_from_config
    from repro.serving import ContinuousBatchingEngine

    cfg = get_config(name).replace(compute_dtype="float32")
    fam = registry.get_family(cfg)
    params = fam.init_params(jax.random.PRNGKey(0), cfg)
    prompts = [np.random.RandomState(s).randint(
                   0, cfg.vocab_size, ln).astype(np.int32)
               for s, ln in ((1, 9), (2, 5), (3, 13))]

    def run(cfg_, fast, **kw):
        eng = ContinuousBatchingEngine(cfg_, params, n_slots=2, block_size=8,
                                       max_blocks_per_seq=6,
                                       fast_kernels=fast, **kw)
        for p in prompts:
            eng.submit(p, max_new)
        eng.run()
        return eng

    recs = []
    # autoregressive: kernel path gathers gate/up AND down over the γ-mask
    # tile list; HLO anchor comes from the frozen engine (same accounting
    # scope only at density 1.0 — which the tiny config serves at)
    eng = run(cfg, True)
    frozen = run(cfg, False)
    n_proj = 3 if cfg.ffn_kind == "glu" else 2
    recs.append(_serve_record("ar", name, eng,
                              hlo=hlo_decode_ffn_bytes(frozen, n_proj)))
    # predictor: density < 1 — modeled bytes follow the measured tile
    # density exactly (nvalid is tile-granular); dense HLO anchor scaled
    # by the measured density
    cfgp = cfg.replace_sparsity(predictor="sign", predictor_recall=1.0)
    calib = {"tokens": jax.random.randint(jax.random.PRNGKey(7), (4, 32),
                                          0, cfgp.vocab_size)}
    pred = calibrate_from_config(params, cfgp, calib, tile=1)
    eng = run(cfgp, True, predictor=pred)
    dens = eng.predictor_density()
    recs.append(_serve_record("predictor", name, eng,
                              hlo=dens * hlo_decode_ffn_bytes(frozen,
                                                              n_proj)))
    return recs


def _serve_record(mode: str, name: str, eng, hlo: float) -> Dict:
    measured = eng.weight_io_bytes_per_step()
    modeled = kernel_bytes_per_step(eng)
    dens = (1.0 if not eng._dens_n else eng._dens_sum / eng._dens_n)
    return {"mode": mode, "config": name, "density": dens,
            "measured_bytes": measured, "modeled_bytes": modeled,
            "hlo_bytes": hlo,
            "ratio": modeled / measured if measured else float("inf"),
            "t_memory_v5e": modeled / HBM_BW}


def serving_table(recs: List[Dict]) -> str:
    hdr = ("mode", "config", "density", "measured", "modeled", "hlo",
           "model/meas", "t_mem(v5e)")
    rows = [hdr]
    for r in recs:
        rows.append((r["mode"], r["config"], f"{r['density']:.3f}",
                     f"{r['measured_bytes']:.0f}",
                     f"{r['modeled_bytes']:.0f}", f"{r['hlo_bytes']:.0f}",
                     f"{r['ratio']:.3f}", fmt_t(r["t_memory_v5e"])))
    widths = [max(len(str(row[i])) for row in rows) for i in range(len(hdr))]
    return "\n".join("  ".join(str(c).ljust(w) for c, w in zip(row, widths))
                     for row in rows)


def check_serving(recs: List[Dict], tol: float = 0.15) -> List[str]:
    """The CI gate: kernel-modeled bytes/step within ``tol`` of the
    engine's measured accounting, and the dense-anchored HLO count within
    ``tol`` of measured. Returns violation strings (empty = pass)."""
    out = []
    for r in recs:
        if abs(r["ratio"] - 1.0) > tol:
            out.append(f"{r['mode']}: kernel-modeled bytes/step "
                       f"{r['modeled_bytes']:.0f} vs measured "
                       f"{r['measured_bytes']:.0f} (ratio {r['ratio']:.3f} "
                       f"outside 1±{tol})")
        hr = (r["hlo_bytes"] / r["measured_bytes"] if r["measured_bytes"]
              else float("inf"))
        if abs(hr - 1.0) > tol:
            out.append(f"{r['mode']}: HLO-counted bytes/step "
                       f"{r['hlo_bytes']:.0f} vs measured "
                       f"{r['measured_bytes']:.0f} (ratio {hr:.3f} "
                       f"outside 1±{tol})")
    return out


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("path", nargs="?", default="experiments/dryrun_results.jsonl")
    ap.add_argument("--md", action="store_true")
    ap.add_argument("--serving", action="store_true",
                    help="serving decode bytes-per-step roofline "
                         "(fused kernels forced on)")
    ap.add_argument("--check", action="store_true",
                    help="with --serving: exit nonzero unless modeled / "
                         "measured / HLO bytes-per-step agree within --tol")
    ap.add_argument("--tol", type=float, default=0.15)
    ap.add_argument("--config", default="tiny-relu")
    args = ap.parse_args()
    if args.serving or args.check:
        recs = serving_records(args.config)
        print(serving_table(recs))
        if args.check:
            bad = check_serving(recs, args.tol)
            for v in bad:
                print("VIOLATION:", v, file=sys.stderr)
            if bad:
                sys.exit(1)
            print(f"roofline check OK (tol {args.tol})")
        return
    recs = load(args.path)
    print(table(recs, md=args.md))
    bad = [r for r in recs if "error" in r]
    over = [r for r in recs if "error" not in r
            and r["peak_bytes_per_chip"] > HBM_GB * 1e9]
    print(f"\n{len(recs)} cells: {len(recs) - len(bad)} compiled, "
          f"{len(bad)} errors, {len(over)} over {HBM_GB:.0f} GB HBM",
          file=sys.stderr)


if __name__ == "__main__":
    main()
