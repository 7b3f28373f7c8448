#!/usr/bin/env python3
"""Serve a full-width OPT-1.3B on a TPU through the serving entry points.

    python chip_smoke.py             # one chip
    python chip_smoke.py --chips 4   # (1, 4) tensor-parallel engine vs one chip

One chip: ``serve_api.build_engine`` builds ``opt-1.3b`` (24 layers, d_model
2048, d_ff 8192, vocab 50272, bf16) from seeded random weights; an in-process
``ApiServer`` over ``AsyncServingEngine`` answers 4 concurrent
``/v1/generate`` streams (3 greedy, 1 seeded-sampled); then 2 requests go to
the engine directly with γ-window reuse (``reuse_window=4``). Checks: the
engine chose the Pallas kernels, the compiled decode step holds both
kernels, the reuse requests read fewer FFN weights than dense, and the first
greedy stream's logprobs agree with an f32 ``model_forward`` at ``highest``
matmul precision on the same chip within ``LOGPROB_TOL``.

``--chips 4`` runs only the tensor-parallel comparison: the engine on a
``(1, 4)`` mesh against the one-chip engine on device 0, both in f32 at
``highest`` precision, greedy tokens equal and logprobs within
``TP_LOGPROB_TOL``.

Everything runs in this one process, which holds the chip(s). Any failed
check raises, so the script exits non-zero; only a run whose every phase
passed prints the last line ``{"ok": true, "device": {...}}``. Without a
TPU it exits 1 and prints no such line.
"""
from __future__ import annotations

import argparse
import asyncio
import json
import math
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

ARCH = "opt-1.3b"
MAX_NEW = 32
# served bf16 logprob vs the f32 `highest` reference, in nats (CHANGES.md):
# bf16 logits round by up to 2^-7 at magnitude 2-4 and 24 layers of bf16
# activations compound it; a wrong tile, block or position costs O(1) nats
LOGPROB_TOL = 0.1
# f32 `highest` TP engine vs f32 `highest` one-chip engine: only summation
# order differs (f32 spacing at logit magnitude 4 is 5e-7)
TP_LOGPROB_TOL = 1e-3
HTTP_TIMEOUT_S = 600


def log(msg: str) -> None:
    print(f"chip_smoke: {msg}", flush=True)


class CompileClock:
    """Sums backend compile time (persistent-cache reads included) and
    counts persistent-cache hits, from jax.monitoring events."""

    def __init__(self):
        import jax
        self.seconds = 0.0
        self.n = 0
        self.hits = 0

        def on_duration(event, duration, **_):
            if event == "/jax/core/compile/backend_compile_duration":
                self.seconds += duration
                self.n += 1

        def on_event(event, **_):
            if event == "/jax/compilation_cache/cache_hits":
                self.hits += 1

        jax.monitoring.register_event_duration_secs_listener(on_duration)
        jax.monitoring.register_event_listener(on_event)

    def __str__(self) -> str:
        return (f"{self.seconds:.1f}s over {self.n} compiles, "
                f"{self.hits} persistent-cache hits")


def engine_args(arch: str, seed: int, *, f32: bool = False):
    from repro.launch.serve_api import parse_args
    argv = ["--arch", arch, "--n-slots", "4", "--block-size", "16",
            "--max-blocks", "16", "--init-seed", str(seed)]
    return parse_args(argv + (["--f32"] if f32 else []))


def prompts(vocab: int, lengths, seed: int):
    import numpy as np
    rng = np.random.RandomState(seed)
    return [[int(t) for t in rng.randint(0, vocab, n)] for n in lengths]


def describe(eng) -> None:
    import jax
    leaves = jax.tree.leaves(eng.params)
    n = sum(x.size for x in leaves)
    nbytes = sum(x.nbytes for x in leaves)
    dtypes = sorted({str(x.dtype) for x in leaves})
    log(f"model {eng.cfg.name}: {eng.cfg.n_layers} layers, d_model "
        f"{eng.cfg.d_model}, d_ff {eng.cfg.d_ff}, vocab {eng.cfg.vocab_size};"
        f" {n} parameters, {nbytes} bytes ({'/'.join(dtypes)}); "
        f"fast_kernels={eng.fast_kernels}")


async def serve_http(eng, bodies):
    """Start ApiServer on port 0 and stream every body concurrently."""
    from repro.launch.serve_api import ApiServer
    from repro.launch.serve_smoke_client import stream_client
    from repro.serving import AsyncServingEngine

    async with AsyncServingEngine(eng) as api:
        server = ApiServer(api, mode="plain")
        await server.start("127.0.0.1", 0)
        try:
            return await asyncio.wait_for(
                asyncio.gather(*[stream_client(server.port, b)
                                 for b in bodies]), HTTP_TIMEOUT_S)
        finally:
            await server.aclose()


def check_stream(i: int, body: dict, tokens, final) -> None:
    if final is None:
        raise AssertionError(f"request {i}: stream ended without a final "
                             f"event after {len(tokens)} tokens")
    if tokens != final["tokens"] or len(tokens) != body["max_new"]:
        raise AssertionError(f"request {i}: streamed {len(tokens)} tokens, "
                             f"final event {len(final['tokens'])}, wanted "
                             f"{body['max_new']}")
    lps = final["logprobs"]
    if not all(math.isfinite(x) and x <= 1e-6 for x in lps):
        raise AssertionError(f"request {i}: logprobs not finite and <= 0: "
                             f"{lps}")


def decode_step_text(eng) -> str:
    """Compiled HLO of the engine's plain decode step at its own shapes."""
    import jax.numpy as jnp
    tokens, pos, table, refresh = eng.scheduler.batch_arrays()
    temps, tks, tps, keys, gen = eng.scheduler.sampling_arrays()
    lowered = eng._decode.lower(
        eng.params, eng.pages, jnp.asarray(table), jnp.asarray(tokens),
        jnp.asarray(pos), eng.masks, jnp.asarray(refresh),
        jnp.asarray(temps), jnp.asarray(tks), jnp.asarray(tps),
        jnp.asarray(keys), jnp.asarray(gen))
    return lowered.compile().as_text()


def check_decode_kernels(eng) -> None:
    text = decode_step_text(eng)
    n_custom = text.count('custom_call_target="tpu_custom_call"')
    missing = [k for k in ("paged_window_attention", "fused_sparse_ffn")
               if k not in text]
    log(f"compiled decode step: {n_custom} Pallas custom calls "
        f"(tpu_custom_call); kernels missing: {missing or 'none'}")
    if n_custom < 2 or missing:
        raise AssertionError("the decode step does not run both Pallas "
                             "kernels on the chip")


def reference_logprobs(cfg, seed: int, prompt, tokens):
    """f32 model_forward at `highest` precision over prompt + tokens[:-1]:
    the log-softmax of each served token at the position that produced it."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.models import registry

    cfg32 = cfg.replace(compute_dtype="float32")
    fam = registry.get_family(cfg32)
    params = fam.init_params(jax.random.PRNGKey(seed), cfg32)
    seq = jnp.asarray([list(prompt) + list(tokens[:-1])], jnp.int32)
    with jax.default_matmul_precision("highest"):
        logits = jax.jit(
            lambda p, t: fam.model_forward(p, {"tokens": t}, cfg32))(params,
                                                                     seq)
    lsm = jax.nn.log_softmax(logits[0, :, :cfg.vocab_size], axis=-1)
    rows = np.arange(len(tokens)) + len(prompt) - 1
    out = np.asarray(lsm[rows, np.asarray(tokens)], np.float64)
    del params, logits, lsm
    return out


def one_chip(arch: str, seed: int) -> None:
    import jax
    import numpy as np
    from repro.launch.serve_api import build_engine

    t0 = time.perf_counter()
    eng = build_engine(engine_args(arch, seed))
    jax.block_until_ready(eng.params)
    describe(eng)
    log(f"engine built in {time.perf_counter() - t0:.1f}s")
    if eng.fast_kernels is not True:
        raise AssertionError("the engine did not choose the Pallas kernels")

    # prompt lengths span 32-200 tokens in four 16-token block counts
    # (3, 6, 10, 13), so whole-prompt prefill compiles four shapes
    vocab = eng.cfg.vocab_size
    http_prompts = prompts(vocab, (200, 150, 96, 40), seed)
    bodies = [{"prompt": p, "max_new": MAX_NEW, "stream": True}
              for p in http_prompts]
    bodies[3].update(temperature=0.8, top_p=0.95, seed=7)
    t0 = time.perf_counter()
    results = asyncio.run(serve_http(eng, bodies))
    dt = time.perf_counter() - t0
    for i, (body, (tokens, final)) in enumerate(zip(bodies, results)):
        check_stream(i, body, tokens, final)
    n_tok = sum(len(t) for t, _ in results)
    ttfts = [round(f["ttft_s"], 3) for _, f in results]
    log(f"HTTP: 4 concurrent /v1/generate streams (3 greedy, 1 sampled), "
        f"{n_tok} tokens in {dt:.1f}s wall including compiles; ttft_s "
        f"{ttfts}")

    # γ-window reuse straight through the engine: between refreshes the
    # fused kernel gathers only the window mask's tiles
    reuse_prompts = prompts(vocab, (199, 37), seed + 1)
    uids = [eng.submit(p, MAX_NEW, reuse_window=4) for p in reuse_prompts]
    res = eng.run()
    saved = [1.0 - res[u].ffn_read_fraction for u in uids]
    log(f"reuse_window=4: per-request FFN weight reads saved "
        f"{[round(s, 4) for s in saved]}; engine weight_io_saved() "
        f"{eng.weight_io_saved():.4f}; measured FFN tile density "
        f"(tiles with a live unit) {eng.tile_activity_rate():.4f}")
    if not (min(saved) > 0 and eng.weight_io_saved() > 0):
        raise AssertionError("γ-window reuse read every FFN weight")

    check_decode_kernels(eng)

    prompt0 = http_prompts[0]
    toks0 = results[0][1]["tokens"]
    served = np.asarray(results[0][1]["logprobs"], np.float64)
    cfg = eng.cfg
    del eng, res  # free the served weights before the f32 reference
    ref = reference_logprobs(cfg, seed, prompt0, toks0)
    err = np.abs(served - ref)
    log(f"greedy request 0 vs f32 highest-precision model_forward: max "
        f"|logprob diff| {err.max():.5f} nats, mean {err.mean():.5f} "
        f"(tolerance {LOGPROB_TOL})")
    if not err.max() <= LOGPROB_TOL:
        raise AssertionError("served logprobs disagree with the reference")


def four_chip(arch: str, seed: int) -> None:
    import dataclasses
    import warnings

    import jax
    import numpy as np
    from repro.launch.mesh import make_host_mesh
    from repro.launch.serve_api import build_engine
    from repro.serving import ContinuousBatchingEngine

    with jax.default_matmul_precision("highest"):
        one = build_engine(engine_args(arch, seed, f32=True))
        describe(one)
        mesh = make_host_mesh(1, 4, strict=True)
        with warnings.catch_warnings(record=True) as caught:
            warnings.simplefilter("always")
            tp = ContinuousBatchingEngine(
                one.cfg, one.params,
                config=dataclasses.replace(one.config, mesh=mesh))
        for w in caught:
            log(f"TP engine warning: {w.message}")
        log(f"TP engine on mesh {dict(mesh.shape)}: fast_kernels="
            f"{tp.fast_kernels} (Pallas kernels are not partitioned under "
            f"a mesh; the sharded XLA path serves); one-chip engine on "
            f"{one.params['embed'].devices()}: fast_kernels="
            f"{one.fast_kernels}")
        if tp.fast_kernels or not one.fast_kernels:
            raise AssertionError("unexpected kernel choice")
        # four prompts in one 16-token block count: one prefill shape each
        ps = prompts(one.cfg.vocab_size, (48, 44, 40, 36), seed)
        out = {}
        for name, eng in (("one-chip", one), ("tp", tp)):
            t0 = time.perf_counter()
            uids = [eng.submit(p, MAX_NEW) for p in ps]
            res = eng.run()
            out[name] = [res[u] for u in uids]
            log(f"{name}: {len(ps)} greedy requests in "
                f"{time.perf_counter() - t0:.1f}s including compiles")
    worst = 0.0
    for i, (a, b) in enumerate(zip(out["one-chip"], out["tp"])):
        if list(a.tokens) != list(b.tokens):
            raise AssertionError(f"request {i}: TP greedy tokens "
                                 f"{list(b.tokens)} != one-chip "
                                 f"{list(a.tokens)}")
        worst = max(worst, float(np.max(np.abs(
            np.asarray(a.logprobs, np.float64)
            - np.asarray(b.logprobs, np.float64)))))
    log(f"TP (1, 4) vs one chip: greedy tokens equal on {len(ps)} requests "
        f"x {MAX_NEW} tokens; max |logprob diff| {worst:.2e} nats "
        f"(tolerance {TP_LOGPROB_TOL})")
    if not worst <= TP_LOGPROB_TOL:
        raise AssertionError("TP logprobs disagree with the one-chip engine")


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the (1, 4) tensor-parallel comparison")
    ap.add_argument("--seed", type=int, default=0,
                    help="seed of the random weights and prompts")
    args = ap.parse_args()

    import jax
    from repro.launch.compile_cache import use_compile_cache

    devices = jax.devices()
    platform = devices[0].platform
    if platform != "tpu":
        print(f"chip_smoke: needs a TPU; JAX found platform {platform!r}",
              file=sys.stderr)
        return 1
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} "
              f"devices, found {len(devices)}", file=sys.stderr)
        return 1
    log(f"devices: {len(devices)} x {devices[0].device_kind}; jax "
        f"{jax.__version__}; compile cache {use_compile_cache()}")
    clock = CompileClock()
    t0 = time.perf_counter()
    if args.chips == 4:
        four_chip(ARCH, args.seed)
    else:
        one_chip(ARCH, args.seed)
    stats = devices[0].memory_stats() or {}
    log(f"compile time {clock}; device 0 peak_bytes_in_use "
        f"{stats.get('peak_bytes_in_use', 'not reported')}; total "
        f"{time.perf_counter() - t0:.1f}s")
    print(json.dumps({"ok": True, "device": {
        "platform": platform, "kind": devices[0].device_kind,
        "count": len(devices)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
