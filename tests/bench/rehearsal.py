"""A tiny OPT-like cell for rehearsing the harness's host logic on the CPU.

The shapes are toy sizes: this measures nothing, it drives the same code
paths as a chip run (weights, engine, warm-up, the asyncio window, the
reference check) with the engine's XLA decode path in place of the
kernels."""
import json
import time

from bench import harness, spec
from bench.spec import BENCH

MC = dict(json.loads((BENCH / "configs" / "opt-1.3b.json").read_text()),
          hidden_size=128, ffn_dim=512, num_attention_heads=16,
          num_hidden_layers=2, vocab_size=512, max_position_embeddings=256,
          word_embed_proj_dim=128)
CELL = {"n_slots": 4, "block_size": 16, "max_len": 128,
        "mean_logit_gap_limit": 5e-5, "rate_per_s": 8.0}
BACKLOG = {"arrival": "backlog", "backlog_extra": 2, "pool": 256,
           "prompt": {"median": 24, "sigma": 0.5, "min": 8, "max": 64},
           "output": {"median": 16, "sigma": 0.5, "min": 8, "max": 48},
           "sampled_share": 0.25,
           "sampling": {"temperature": 0.8, "top_p": 0.95}}
POISSON = dict(BACKLOG, arrival="poisson", warmup_s=0.5)


def tiny_cfg(mc):
    from repro.configs import get_config
    return get_config("opt-1.3b").replace(
        n_layers=mc["num_hidden_layers"], d_model=mc["hidden_size"],
        n_heads=mc["num_attention_heads"],
        n_kv_heads=mc["num_attention_heads"], d_ff=mc["ffn_dim"],
        vocab_size=mc["vocab_size"],
        max_seq_len=mc["max_position_embeddings"], param_dtype="bfloat16")


def tiny_spec(mix=BACKLOG, name="tiny.backlog", per_layer=()):
    e2e = [("output_tokens_per_s", "tokens/s"), ("setup_s", "s")]
    return spec.Spec(name=name, chips=1, cell=dict(CELL), mc=MC, mix=mix,
                     end_to_end=[{"name": n, "unit": u} for n, u in e2e],
                     per_layer=list(per_layer))


def run(monkeypatch, seed, seconds=2.0, mix=BACKLOG, **kw):
    monkeypatch.setattr(harness, "program_config", tiny_cfg)
    return harness.run_cell(tiny_spec(mix), seed, seconds,
                            kw.pop("trace", False), t_start=time.perf_counter(),
                            require_kernels=False, **kw)
