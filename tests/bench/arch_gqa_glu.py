"""A second decoder architecture, for the tests only: grouped-query
attention with rotary positions, RMSNorm, a gated ReLU FFN and a head of
its own (the block of a relufied Llama), which the program's dense family
runs. Its file is ``gqa_glu.json`` beside this module.

It enters the benchmark as ``bench/arch/<model_type>.py`` does, through
the same four functions and with a plain float32 reference of its own,
importing nothing of the program. Its weights have the program's padded
layout: query heads in ``bench/weights.py``'s ``head_slots``, padded
slots and vocabulary rows zero, so the reference runs over every slot.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from bench.reference import mm
from bench.weights import head_slots, padded_vocab, vocab_rows

MODEL_TYPE = "gqa_glu"


def _head_dim(mc: dict) -> int:
    return mc.get("head_dim", mc["hidden_size"] // mc["num_attention_heads"])


def dims(mc: dict) -> dict:
    d, H, L = (mc["hidden_size"], mc["num_attention_heads"],
               mc["num_hidden_layers"])
    return {"n_layers": L, "d_model": d, "n_heads": H,
            "n_kv_heads": mc["num_key_value_heads"],
            "head_dim": _head_dim(mc), "d_ff": mc["intermediate_size"],
            "n_proj": 3,
            "vocab_size": mc["vocab_size"],
            "init_std": mc["initializer_range"],
            "calls": {"fused_sparse_ffn": L, "paged_window_attention": L}}


def program_fields(mc: dict) -> dict:
    if mc["tie_word_embeddings"]:
        raise ValueError("this architecture has a head of its own")
    fields = {"head_dim": mc["head_dim"]} if "head_dim" in mc else {}
    return dict(fields, d_model=mc["hidden_size"],
                d_ff=mc["intermediate_size"],
                n_heads=mc["num_attention_heads"],
                n_kv_heads=mc["num_key_value_heads"],
                vocab_size=mc["vocab_size"],
                max_seq_len=mc["max_position_embeddings"],
                activation=mc["hidden_act"], ffn_kind="glu",
                norm_kind="rmsnorm", use_rope=True,
                rope_theta=mc["rope_theta"], tie_embeddings=False,
                post_norm_relu=False, qkv_bias=False, qk_norm=False,
                sliding_window=0)


def shapes(mc: dict) -> dict:
    d, L = mc["hidden_size"], mc["num_hidden_layers"]
    hp, q_real, kvp, kv_real = head_slots(mc["num_attention_heads"],
                                          mc["num_key_value_heads"])
    hd, F = _head_dim(mc), mc["intermediate_size"]
    vp, rows = padded_vocab(mc["vocab_size"]), vocab_rows(mc["vocab_size"])
    return {
        "embed": ((vp, d), None, rows),
        "unembed": ((vp, d), None, rows),
        "final_norm/scale": ((d,), 1.0),
        "layers/ln1/scale": ((L, d), 1.0),
        "layers/ln2/scale": ((L, d), 1.0),
        "layers/attn/wq": ((L, d, hp, hd), None, (2, q_real)),
        "layers/attn/wk": ((L, d, kvp, hd), None, (2, kv_real)),
        "layers/attn/wv": ((L, d, kvp, hd), None, (2, kv_real)),
        "layers/attn/wo": ((L, hp, hd, d), None, (1, q_real)),
        "layers/ffn/wg": ((L, d, F), None),
        "layers/ffn/wu": ((L, d, F), None),
        "layers/ffn/wd": ((L, F, d), None),
    }


# ---------------------------------------------------------------------------
# the plain reference


def _rms(x, scale, eps):
    var = jnp.mean(jnp.square(x), -1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * scale


def _rope(x, theta):
    """Rotate each head's two halves by position: x (S, heads, hd)."""
    S, _, hd = x.shape
    inv = 1.0 / theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    ang = jnp.arange(S, dtype=jnp.float32)[:, None] * inv
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    x1, x2 = x[..., :hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _layer(x, lw, *, eps, theta, control):
    lw = jax.tree.map(lambda a: a.astype(jnp.float32), lw)
    S = x.shape[0]
    H, hd = lw["attn"]["wq"].shape[1:]  # every slot: padded ones are zero
    K = lw["attn"]["wk"].shape[1]
    h = _rms(x, lw["ln1"]["scale"], eps)
    q = _rope(mm("sd,dhe->she", h, lw["attn"]["wq"], control, -1, 0), theta)
    k = _rope(mm("sd,dhe->she", h, lw["attn"]["wk"], control, -1, 0), theta)
    v = mm("sd,dhe->she", h, lw["attn"]["wv"], control, -1, 0)
    # query head j reads KV head j // (H / K)
    k, v = jnp.repeat(k, H // K, axis=1), jnp.repeat(v, H // K, axis=1)
    s = mm("she,the->hst", q * (hd ** -0.5), k, control, -1, -1)
    causal = jnp.arange(S)[:, None] >= jnp.arange(S)[None, :]
    p = jax.nn.softmax(jnp.where(causal[None], s, -jnp.inf), axis=-1)
    o = mm("hst,the->she", p, v, control, -1, 0)
    x = x + mm("she,hed->sd", o, lw["attn"]["wo"], control, (-2, -1),
               (0, 1))
    h = _rms(x, lw["ln2"]["scale"], eps)
    g = jax.nn.relu(mm("sd,df->sf", h, lw["ffn"]["wg"], control, -1, 0))
    u = mm("sd,df->sf", h, lw["ffn"]["wu"], control, -1, 0)
    return x + mm("sf,fd->sd", g * u, lw["ffn"]["wd"], control, -1, 0)


@functools.partial(jax.jit,
                   static_argnames=("vocab", "eps", "theta", "control"))
def _logits(w, tokens, rows, *, vocab, eps, theta, control):
    x = w["embed"][:vocab].astype(jnp.float32)[tokens]
    x, _ = jax.lax.scan(
        lambda x, lw: (_layer(x, lw, eps=eps, theta=theta,
                              control=control), None), x, w["layers"])
    h = _rms(x[rows], w["final_norm"]["scale"].astype(jnp.float32), eps)
    head = w["unembed"][:vocab].astype(jnp.float32)
    return mm("rd,vd->rv", h, head, control, -1, -1)


def logits_at(w, tokens, rows, *, mc: dict, control: bool):
    return _logits(w, tokens, rows, vocab=mc["vocab_size"],
                   eps=mc["rms_norm_eps"], theta=mc["rope_theta"],
                   control=control)
