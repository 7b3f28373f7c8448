"""A tiny cell for rehearsing the harness's host logic on the CPU.

The shapes are toy sizes: this measures nothing, it drives the same code
paths as a chip run (weights, engine, warm-up, the asyncio window, the
reference check) with the engine's XLA decode path in place of the
kernels. ``MC`` is a tiny OPT; another architecture's file runs through
the same calls."""
import json
import time

import jax
import jax.numpy as jnp

from bench import arch, harness, spec
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


def alter_tokens(engine):
    """A token altered where it is produced: the decode step's output."""
    inner = engine._decode

    def decode(*args):
        out = inner(*args)
        return ((out[0] + 1) % engine.cfg.vocab_size,) + tuple(out[1:])
    engine._decode = decode


def keep_state(engine):
    """A step that returns its state unchanged: the KV pool as it was."""
    inner = engine._decode

    def decode(params, pages, *rest):
        kept = jax.tree.map(jnp.copy, pages)
        out = inner(params, pages, *rest)
        return out[:2] + (kept,) + tuple(out[3:])
    engine._decode = decode


FAULTS = [alter_tokens, keep_state]


def tiny_cfg(mc):
    """``harness.program_config`` without its check against the registry:
    the registry's model with every field the architecture's module takes
    from the file, at the file's toy sizes."""
    from repro.configs import get_config
    return get_config(mc["registry_name"]).replace(
        n_layers=arch.dims(mc)["n_layers"], param_dtype="bfloat16",
        **arch.load(mc).program_fields(mc))


def use_arch(monkeypatch, module):
    """Let the harness find ``module`` by its ``MODEL_TYPE``, as it finds
    ``bench/arch/<model_type>.py``."""
    found = arch.load
    monkeypatch.setattr(arch, "load", lambda mc: (
        module if mc.get("model_type") == module.MODEL_TYPE else found(mc)))


def tiny_spec(mix=BACKLOG, name="tiny.backlog", per_layer=(), mc=MC,
              cell=CELL):
    e2e = [("output_tokens_per_s", "tokens/s"), ("setup_s", "s")]
    return spec.Spec(name=name, chips=1, cell=dict(cell), mc=mc, mix=mix,
                     end_to_end=[{"name": n, "unit": u} for n, u in e2e],
                     per_layer=list(per_layer))


def run(monkeypatch, seed, seconds=2.0, mix=BACKLOG, mc=MC, cell=CELL,
        trace=False, per_layer=(), **kw):
    monkeypatch.setattr(harness, "program_config", tiny_cfg)
    return harness.run_cell(tiny_spec(mix, mc=mc, cell=cell,
                                      per_layer=per_layer),
                            seed, seconds, trace,
                            t_start=time.perf_counter(),
                            require_kernels=False, **kw)
