"""Benchmark harness — one module per paper table/figure.

Each module runs in its OWN subprocess (the XLA CPU JIT accumulates code
memory across eager stats passes; isolation keeps the suite within RAM).
Prints ``name,us_per_call,derived`` CSV rows; full results in
experiments/bench_*.json. Trained tiny models are disk-cached and shared.
"""
from __future__ import annotations

import os
import subprocess
import sys
import time

MODULES = [
    "benchmarks.fig1_sparsity",
    "benchmarks.fig2_actfn_spectrum",
    "benchmarks.table1_flops",
    "benchmarks.fig5_preactivation",
    "benchmarks.fig6_recovery",
    "benchmarks.fig7_aggregated",
    "benchmarks.fig7_spec_decode",
    "benchmarks.fig8_shifted_relu",
    "benchmarks.fig9_flops_latency",
    "benchmarks.fig10_optimal_gamma",
    "benchmarks.appE_scaling",
    "benchmarks.serving_throughput",
    "benchmarks.slo_traffic",
    "benchmarks.predictor_sparsity",
    "benchmarks.kernel_bench",
]

# training-free modules that exercise the kernel + serving hot paths; the CI
# benchmark-smoke job runs these (BENCH_SMOKE=1 shrinks workloads further and
# makes fig7_spec_decode use random-init tiny models, so the engine's
# speculative path is exercised on every push)
SMOKE_MODULES = [
    "benchmarks.fig9_flops_latency",
    "benchmarks.fig10_optimal_gamma",
    "benchmarks.fig7_spec_decode",
    "benchmarks.serving_throughput",
    "benchmarks.slo_traffic",
    "benchmarks.predictor_sparsity",
    "benchmarks.kernel_bench",
]


def run_module(mod_name: str) -> None:
    import importlib
    # script invocation puts benchmarks/ (not the repo root) on sys.path;
    # make `import benchmarks.*` work either way
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    if root not in sys.path:
        sys.path.insert(0, root)
    mod = importlib.import_module(mod_name)
    for r in mod.run():
        print(r, flush=True)


PR_TAG = os.environ.get("BENCH_PR", "pr10")


def write_trajectory(tag: str = PR_TAG) -> str:
    """Collapse experiments/bench_*.json into a repo-root ``BENCH_<pr>.json``
    so the perf trajectory is tracked PR-over-PR in git (the experiments/
    files are gitignored run artifacts; this one is committed). Headline
    numbers: serving throughput, the weight-I/O savings of every serving
    mode, and the prefix cache's hit rate / prefill-tokens-saved."""
    import glob
    import json

    sources = {}
    for path in sorted(glob.glob("experiments/bench_*.json")):
        try:
            with open(path) as f:
                sources[os.path.basename(path)] = json.load(f)
        except (OSError, ValueError):  # a failed module's partial file
            continue
    serving = sources.get("bench_serving.json", {})
    kernels = sources.get("bench_kernels.json", {})
    slo = sources.get("bench_slo.json", {})
    out = {
        "pr": tag,
        "headline": {
            "legacy_tokens_per_s": serving.get("legacy_tokens_per_s"),
            "serving_tokens_per_s": serving.get("cb_rate0_tokens_per_s"),
            "cb_speedup_vs_legacy": serving.get("cb_rate0_speedup"),
            "weight_io_saved_gamma4": serving.get("cb_gamma4_io_saved"),
            "spec_s_agg_gamma4": serving.get("cb_spec_gamma4_s_agg"),
            "weight_io_saved_predictor": serving.get("cb_predictor_io_saved"),
            "prefix_cache_tokens_per_s":
                serving.get("cb_prefix_cache_tokens_per_s"),
            "prefix_hit_rate": serving.get("cb_prefix_cache_hit_rate"),
            "prefill_tokens_saved":
                serving.get("cb_prefix_cache_prefill_tokens_saved"),
            # MoE through the engine (ISSUE 9): throughput + the
            # activated-expert fraction of FFN weight I/O per step
            "moe_tokens_per_s": serving.get("cb_moe_tokens_per_s"),
            "moe_expert_io_fraction":
                serving.get("cb_moe_expert_io_fraction"),
            "api_stream_tokens_per_s":
                serving.get("cb_api_stream_tokens_per_s"),
            "api_ttft_ms": serving.get("cb_api_stream_ttft_ms"),
            "api_tpot_ms": serving.get("cb_api_stream_tpot_ms"),
            # engine-side span percentiles from the obs histograms, merged
            # across every continuous-batching case (serving_throughput.py)
            "ttft_p50_ms": serving.get("serving_ttft_p50_ms"),
            "ttft_p99_ms": serving.get("serving_ttft_p99_ms"),
            "tpot_p50_ms": serving.get("serving_tpot_p50_ms"),
            "tpot_p99_ms": serving.get("serving_tpot_p99_ms"),
            "queue_wait_p50_ms": serving.get("serving_queue_wait_p50_ms"),
            "queue_wait_p99_ms": serving.get("serving_queue_wait_p99_ms"),
            # SLO scheduling (ISSUE 10): interactive-class goodput under a
            # step-based TTFT SLO, the FIFO baseline at the same offered
            # load, their gap (gated > 0), and the preemptions exercised
            "slo_goodput": slo.get("slo_goodput"),
            "slo_goodput_fifo": slo.get("slo_goodput_fifo"),
            "slo_goodput_gain": slo.get("slo_goodput_gain"),
            "preemption_count": slo.get("preemption_count"),
            "kernel_bytes_ratio": kernels.get("kernel_bytes_ratio"),
            "kernel_ffn_fused_us":
                (kernels.get("ffn_fused_kernel") or {}).get("us_per_call"),
            "kernel_attn_fused_us":
                (kernels.get("attn_fused_kernel") or {}).get("us_per_call"),
        },
        "sources": sources,
    }
    fname = f"BENCH_{tag.upper()}.json"
    with open(fname, "w") as f:
        json.dump(out, f, indent=2)
    return fname


def main() -> None:
    os.makedirs("experiments", exist_ok=True)
    smoke = "--smoke" in sys.argv
    args = [a for a in sys.argv[1:] if a not in ("--smoke", "--all")]
    if args:
        if smoke:
            os.environ["BENCH_SMOKE"] = "1"  # before the module import
        from repro.launch.compile_cache import use_compile_cache
        use_compile_cache()
        run_module(args[0])
        return
    print("name,us_per_call,derived", flush=True)
    failures = 0
    env = dict(os.environ)
    env.setdefault("PYTHONPATH", "src")
    if smoke:
        env["BENCH_SMOKE"] = "1"
    for mod_name in (SMOKE_MODULES if smoke else MODULES):
        t0 = time.time()
        r = subprocess.run([sys.executable, "-m", "benchmarks.run", mod_name],
                           capture_output=True, text=True, env=env)
        dt = time.time() - t0
        if r.returncode == 0:
            sys.stdout.write(r.stdout)
            print(f"# {mod_name} done in {dt:.1f}s", file=sys.stderr)
        else:
            failures += 1
            print(f"# FAILED {mod_name}:\n{r.stderr[-2000:]}", file=sys.stderr)
        sys.stdout.flush()
    print(f"# wrote {write_trajectory()}", file=sys.stderr)
    if failures:
        sys.exit(1)


if __name__ == "__main__":
    main()
