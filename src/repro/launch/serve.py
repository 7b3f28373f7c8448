"""Production serving launcher: prefill + decode steps on the pod mesh, with
the paper's sparse-inference config (relufied weights, tile capacities).

  python -m repro.launch.serve --arch deepseek-67b --shape decode_32k \
      --sparse-density 0.25 [--multi-pod]
  python -m repro.launch.serve --arch qwen3-4b --smoke --tokens 32   # CPU
  python -m repro.launch.serve --arch qwen3-4b --smoke --continuous  # CB path
  python -m repro.launch.serve --arch qwen3-4b --smoke --speculative # spec
  python -m repro.launch.serve --arch qwen3-4b --smoke \
      --predictor sign --target-recall 0.99                # predictor mode
  python -m repro.launch.serve --arch qwen3-4b --smoke \
      --prefill-chunk 16 --prefix-cache   # chunked prefill + prefix reuse
  python -m repro.launch.serve --arch qwen3-4b --smoke --continuous \
      --mesh 1,8    # tensor-parallel sharded serving on a (data,model) mesh
  python -m repro.launch.serve --arch mixtral-8x22b --smoke --continuous \
      # MoE through the engine: routed experts as structured sparsity
"""
from __future__ import annotations

import argparse


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--shape", default="decode_32k")
    ap.add_argument("--sparse-density", type=float, default=0.0,
                    help="FFN tile density; 0 = dense serving")
    ap.add_argument("--reuse-window", type=int, default=0)
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--continuous", action="store_true",
                    help="smoke the continuous-batching paged-cache engine "
                         "(any family declaring the 'paged_decode' serving "
                         "capability: dense + moe)")
    ap.add_argument("--speculative", action="store_true",
                    help="smoke the engine's speculative mode: a 1-layer "
                         "draft proposes γ tokens per slot, the target "
                         "verifies each window in one forward (implies "
                         "--continuous)")
    ap.add_argument("--gamma", type=int, default=4,
                    help="draft length γ for --speculative")
    ap.add_argument("--predictor", choices=["none", "sign", "lowrank"],
                    default="none",
                    help="predictor serving mode: skip up+down projection "
                         "weight reads for neurons a calibrated activity "
                         "predictor marks inactive (implies --continuous; "
                         "relufies soft-activation archs first)")
    ap.add_argument("--target-recall", type=float, default=0.99,
                    help="calibration recall target for --predictor")
    ap.add_argument("--prefill-chunk", type=int, default=0,
                    help="chunked prefill: admit prompts one fixed-size "
                         "chunk per engine step, interleaved with decode "
                         "(0 = whole-prompt prefill; implies --continuous)")
    ap.add_argument("--prefix-cache", action="store_true",
                    help="reuse KV blocks across requests sharing a "
                         "block-aligned prompt prefix (the smoke workload "
                         "then shares a system prompt; implies "
                         "--prefill-chunk 16 unless set)")
    ap.add_argument("--mesh", default=None, metavar="DATA,MODEL",
                    help="serve the continuous-batching engine on a "
                         "(data, model) device mesh: weights TP-sharded "
                         "over 'model' via the serve-mode rules, paged KV "
                         "pool blocks over 'data' (implies --continuous; "
                         "RAISES if the shape needs more devices than "
                         "exist — no silent single-device fallback)")
    ap.add_argument("--tokens", type=int, default=16)
    args = ap.parse_args()
    if args.prefix_cache and args.prefill_chunk == 0:
        args.prefill_chunk = 16
    if (args.speculative or args.predictor != "none" or args.prefill_chunk
            or args.mesh):
        args.continuous = True
    mesh_shape = None
    if args.mesh:
        try:
            mesh_shape = tuple(int(x) for x in args.mesh.split(","))
            assert len(mesh_shape) == 2 and min(mesh_shape) >= 1
        except (ValueError, AssertionError):
            ap.error(f"--mesh expects DATA,MODEL (two positive ints), "
                     f"got {args.mesh!r}")
    if args.speculative and args.predictor != "none":
        ap.error("--speculative and --predictor are mutually exclusive "
                 "serving modes")
    if args.continuous and not args.smoke:
        ap.error("--continuous requires --smoke (the pod-mesh launcher "
                 "lowers the legacy decode cell)")

    import jax
    import jax.numpy as jnp

    from repro.launch.compile_cache import use_compile_cache
    use_compile_cache()
    from repro.configs import SHAPES, get_config, smoke_config
    from repro.core import relufication
    from repro.models import registry

    cfg = smoke_config(args.arch) if args.smoke else get_config(args.arch)
    if args.sparse_density > 0:
        cfg = relufication.relufy_stage2(cfg)
        cfg = relufication.enable_sparse_serving(
            cfg, args.sparse_density, min(1.0, args.sparse_density * 3),
            reuse_window=args.reuse_window)

    if args.smoke and args.continuous:
        import numpy as np
        from repro.serving import ContinuousBatchingEngine, EngineConfig
        from repro.serving.spec_decode import spec_metrics
        if args.predictor != "none":
            from repro.core.activations import is_sparse_activation
            if not is_sparse_activation(cfg.activation):
                cfg = relufication.relufy_stage1(cfg)
            cfg = cfg.replace_sparsity(predictor=args.predictor,
                                       predictor_recall=args.target_recall)
        fam = registry.get_family(cfg)
        params = fam.init_params(jax.random.PRNGKey(0), cfg)
        rng = np.random.RandomState(1)
        lengths = (8, 13, 21)
        if args.prefix_cache:
            # shared system prompt (two full 16-token blocks): request 1
            # prefills it cold, every later admission maps it from the trie
            system = rng.randint(0, cfg.vocab_size, 32)
            prompts = [np.concatenate([system,
                                       rng.randint(0, cfg.vocab_size, s)])
                       for s in lengths]
        else:
            prompts = [rng.randint(0, cfg.vocab_size, s) for s in lengths]
        max_bps = -(-(max(len(p) for p in prompts) + args.tokens) // 16)
        spec_kw = {}
        if args.prefill_chunk:
            spec_kw.update(prefill_chunk=args.prefill_chunk,
                           prefix_cache=args.prefix_cache)
        if args.speculative:
            dcfg = cfg.replace(name=f"{cfg.name}-draft", n_layers=1)
            spec_kw.update(draft_cfg=dcfg,
                           draft_params=fam.init_params(
                               jax.random.PRNGKey(2), dcfg),
                           gamma=args.gamma)
        if args.predictor != "none":
            from repro.predictor import calibrate_from_config
            calib = {"tokens": jax.random.randint(
                jax.random.PRNGKey(7), (4, 32), 0, cfg.vocab_size)}
            # tile=1 = exact row-skipping: observable savings on the tiny
            # smoke models (128-wide tiles are never all-zero at this size)
            spec_kw.update(predictor=calibrate_from_config(
                params, cfg, calib, tile=1))
        if mesh_shape is not None:
            from repro.launch.mesh import make_host_mesh
            # strict: an unsatisfiable --mesh shape is an operator error —
            # raise instead of quietly serving single-device
            spec_kw["mesh"] = make_host_mesh(*mesh_shape, strict=True)
        eng = ContinuousBatchingEngine(cfg, params, config=EngineConfig(
            n_slots=2, block_size=16, max_blocks_per_seq=max_bps,
            track_sparsity=True, **spec_kw))
        uids = [eng.submit(p, args.tokens, reuse_window=args.reuse_window)
                for p in prompts]
        res = eng.run()
        aggs = [eng.trackers[u].aggregated_sparsity() for u in uids]
        print(f"continuous batching served {len(uids)} requests "
              f"({sum(len(res[u].tokens) for u in uids)} tokens); "
              f"per-request aggregated FFN sparsity "
              f"{', '.join(f'{a:.3f}' for a in aggs)}; "
              f"weight I/O saved {eng.weight_io_saved():.1%}")
        if cfg.n_experts:
            print(f"moe routing: {cfg.top_k}/{cfg.n_experts} experts per "
                  f"token (expert I/O fraction "
                  f"{eng.expert_io_fraction():.3f}); activated-expert FFN "
                  f"weight read {eng.weight_io_bytes_per_step():.0f} B/step")
        if mesh_shape is not None:
            print(f"sharded serving on mesh {dict(eng.mesh.shape)}: "
                  f"TP={eng.tp}; per-device FFN weight read "
                  f"{eng.weight_io_bytes_per_step():.0f} B/step "
                  f"(= {eng.weight_io_bytes_per_step(per_device=False):.0f} "
                  f"B total x 1/{eng.ffn_tp})")
        if args.prefix_cache:
            print(f"prefix cache: hit rate {eng.prefix_hit_rate():.1%}; "
                  f"prefill tokens saved {eng.prefill_tokens_saved()} "
                  f"(chunked prefill, chunk={args.prefill_chunk})")
        if args.predictor != "none":
            print(f"predictor={args.predictor} "
                  f"(target recall {args.target_recall}): "
                  f"tile density {eng.predictor_density():.3f}; "
                  f"realized recall {eng.predictor_recall():.4f}; "
                  f"up+down weight I/O saved {eng.weight_io_saved():.1%}; "
                  f"per-request misses "
                  f"{', '.join(str(res[u].pred_misses) for u in uids)}")
        if args.speculative:
            ms = [spec_metrics(res[u], gamma=args.gamma, c=0.1,
                               s_agg=eng.s_agg_window()) for u in uids]
            print(f"speculative gamma={args.gamma}: "
                  f"alpha={np.mean([m.accept_rate for m in ms]):.3f}; "
                  f"target-call reduction "
                  f"{np.mean([m.target_call_reduction for m in ms]):.2f}x; "
                  f"window s_agg={eng.s_agg_window():.3f}; "
                  f"Thm1 sparse-verify speedup "
                  f"{np.mean([m.thm1_speedup for m in ms]):.3f}x")
        from repro.obs import format_statusz
        print("-- final observability snapshot --")
        print(format_statusz(eng), end="")
        return

    if args.smoke:
        from repro.serving.engine import ServeEngine
        fam = registry.get_family(cfg)
        params = fam.init_params(jax.random.PRNGKey(0), cfg)
        eng = ServeEngine(cfg, params, max_len=64 + args.tokens,
                          track_sparsity=True)
        batch = {"tokens": jax.random.randint(jax.random.PRNGKey(1), (2, 8),
                                              0, cfg.vocab_size)}
        if cfg.family == "vlm":
            batch["patches"] = jnp.zeros(
                (2, cfg.n_vision_tokens, cfg.d_model),
                jnp.dtype(cfg.compute_dtype))
        if cfg.family == "encdec":
            batch["frames"] = jnp.zeros((2, cfg.n_audio_frames, cfg.d_model),
                                        jnp.dtype(cfg.compute_dtype))
        res = eng.generate(batch, max_new=args.tokens,
                           reuse_window=args.reuse_window)
        agg = (res.aggregated.aggregated_sparsity()
               if res.aggregated is not None else float("nan"))
        print(f"generated {res.tokens.shape} tokens; aggregated FFN sparsity "
              f"{agg:.3f}")
        return

    from repro.launch import mesh as mesh_lib
    from repro.launch import specs as specs_lib
    shape = SHAPES[args.shape]
    mesh = mesh_lib.make_production_mesh(multi_pod=args.multi_pod)
    with mesh:
        jitted, specs = specs_lib.build_cell(cfg, shape, mesh)
        compiled = jitted.lower(*specs).compile()
    print("serve step compiled for", mesh.shape, "-",
          compiled.memory_analysis())


if __name__ == "__main__":
    main()
