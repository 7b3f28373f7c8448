"""Production training launcher.

On a real TPU pod each host runs this under its runtime; on this container
it runs reduced configs (--smoke) end-to-end. The pjit step, sharding rules,
checkpointing, and relufication stages are identical in both paths.

  python -m repro.launch.train --arch qwen2-7b --shape train_4k \
      --relufy-stage 2 --steps 30000 --ckpt /ckpt/qwen2-relu [--multi-pod]
  python -m repro.launch.train --arch qwen3-4b --smoke --steps 20   # CPU
"""
from __future__ import annotations

import argparse


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--shape", default="train_4k")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--relufy-stage", type=int, default=0, choices=[0, 1, 2])
    ap.add_argument("--shifted-relu", type=float, default=None,
                    help="use ReLU(x - b) with this shift")
    ap.add_argument("--ckpt", default=None)
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--smoke", action="store_true",
                    help="reduced config on local devices (CPU)")
    ap.add_argument("--microbatches", type=int, default=0)
    ap.add_argument("--lr", type=float, default=1.5e-5)  # paper's FT recipe
    args = ap.parse_args()

    import jax

    from repro.launch.compile_cache import use_compile_cache
    use_compile_cache()
    from repro.configs import SHAPES, TrainConfig, get_config, smoke_config
    from repro.core import relufication
    from repro.data.pipeline import DataConfig
    from repro.train.loop import Trainer

    cfg = smoke_config(args.arch) if args.smoke else get_config(args.arch)
    if args.relufy_stage == 1:
        cfg = relufication.relufy_stage1(cfg)
    elif args.relufy_stage == 2:
        cfg = relufication.relufy_stage2(cfg)
    if args.shifted_relu is not None:
        cfg = relufication.shifted_relufy(cfg, args.shifted_relu)

    if args.smoke:
        dc = DataConfig(vocab_size=cfg.vocab_size, seq_len=64, batch_size=4)
        tc = TrainConfig(learning_rate=5e-3, total_steps=args.steps,
                         warmup_steps=5, num_microbatches=1)
        tr = Trainer(cfg, tc, dc, ckpt_dir=args.ckpt)
        rep = tr.run(args.steps)
        print(f"done: {rep.steps} steps, final loss {rep.losses[-1]:.4f}, "
              f"skipped {rep.skipped_steps}, stragglers {rep.straggler_steps}")
        return

    # production pod path: build the sharded step on the 16x16 (or 2x16x16)
    # mesh. Requires the actual TPU runtime; here we validate the build.
    from repro.launch import mesh as mesh_lib
    from repro.launch import specs as specs_lib
    shape = SHAPES[args.shape]
    if args.microbatches:
        import dataclasses
        shape = dataclasses.replace(shape, num_microbatches=args.microbatches)
    mesh = mesh_lib.make_production_mesh(multi_pod=args.multi_pod)
    with mesh:
        jitted, specs = specs_lib.build_cell(
            cfg, shape, mesh,
            tc=TrainConfig(learning_rate=args.lr,
                           num_microbatches=shape.num_microbatches or 1,
                           remat_policy="minimal"))
        compiled = jitted.lower(*specs).compile()
    print("train step compiled for", mesh.shape, "-",
          compiled.memory_analysis())


if __name__ == "__main__":
    main()
