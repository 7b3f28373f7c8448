"""A second decoder architecture, for the tests only: grouped-query
attention with rotary positions, RMSNorm, a gated ReLU FFN and a head of
its own (the block of a relufied Llama), which the program's dense family
runs. Its file is ``gqa_glu.json`` beside this module.

It enters the benchmark as ``bench/arch/<model_type>.py`` does, through
the same four functions and with a plain float32 reference of its own,
importing nothing of the program.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from bench.reference import mm
from bench.weights import padded_vocab

MODEL_TYPE = "gqa_glu"
HEAD_SLOTS = 16  # the program pads query heads to a multiple of this


def dims(mc: dict) -> dict:
    d, H, L = (mc["hidden_size"], mc["num_attention_heads"],
               mc["num_hidden_layers"])
    return {"n_layers": L, "d_model": d, "n_heads": H,
            "n_kv_heads": mc["num_key_value_heads"], "head_dim": d // H,
            "d_ff": mc["intermediate_size"], "n_proj": 3,
            "vocab_size": mc["vocab_size"],
            "init_std": mc["initializer_range"],
            "calls": {"fused_sparse_ffn": L, "paged_window_attention": L}}


def program_fields(mc: dict) -> dict:
    if mc["tie_word_embeddings"]:
        raise ValueError("this architecture has a head of its own")
    return dict(d_model=mc["hidden_size"], d_ff=mc["intermediate_size"],
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
    H, K = mc["num_attention_heads"], mc["num_key_value_heads"]
    if H % HEAD_SLOTS:
        raise ValueError(f"num_attention_heads {H}: the weights would hold "
                         f"the program's padded head slots")
    hd, F = d // H, mc["intermediate_size"]
    vp = padded_vocab(mc["vocab_size"])
    return {
        "embed": ((vp, d), None),
        "unembed": ((vp, d), None),
        "final_norm/scale": ((d,), 1.0),
        "layers/ln1/scale": ((L, d), 1.0),
        "layers/ln2/scale": ((L, d), 1.0),
        "layers/attn/wq": ((L, d, H, hd), None),
        "layers/attn/wk": ((L, d, K, hd), None),
        "layers/attn/wv": ((L, d, K, hd), None),
        "layers/attn/wo": ((L, H, hd, d), None),
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
    H, hd = lw["attn"]["wq"].shape[1:]
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
