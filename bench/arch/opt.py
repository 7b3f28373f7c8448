"""OPT (facebook/opt-*): pre-LN decoder blocks, learned positions, full
multi-head attention, a ReLU MLP and a head tied to the embedding.

The plain reference runs in float32, follows the published OPT decoder
with the configuration file's sizes, reads the benchmark's weights
(``bench/weights.py``) and imports nothing of the program. One layer at a
time goes through a ``lax.scan``, upcast to float32 inside the body, so
the float32 copy never exists for the whole stack. OPT's linear biases
are zero at initialisation and the program has none, so neither side
carries them.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from bench.reference import mm
from bench.weights import padded_vocab, vocab_rows


def dims(mc: dict) -> dict:
    d, H, L = (mc["hidden_size"], mc["num_attention_heads"],
               mc["num_hidden_layers"])
    return {"n_layers": L, "d_model": d, "n_heads": H, "n_kv_heads": H,
            "head_dim": d // H, "d_ff": mc["ffn_dim"], "n_proj": 2,
            "vocab_size": mc["vocab_size"], "init_std": mc["init_std"],
            "calls": {"fused_sparse_ffn": L, "paged_window_attention": L}}


def program_fields(mc: dict) -> dict:
    if (mc["word_embed_proj_dim"] != mc["hidden_size"]
            or not mc["do_layer_norm_before"]):
        raise ValueError("the program runs OPT's pre-LN block without an "
                         "embedding projection")
    return dict(d_model=mc["hidden_size"], d_ff=mc["ffn_dim"],
                n_heads=mc["num_attention_heads"],
                n_kv_heads=mc["num_attention_heads"],
                vocab_size=mc["vocab_size"],
                max_seq_len=mc["max_position_embeddings"],
                activation=mc["activation_function"], ffn_kind="mlp",
                norm_kind="layernorm", use_rope=False, tie_embeddings=True,
                post_norm_relu=False, qkv_bias=False)


def shapes(mc: dict) -> dict:
    """Leaf name -> (shape, fill[, (axis, real entries)]): fill None
    draws N(0, init_std^2), as OPT initialises every projection and
    embedding, the embedding's padded vocabulary rows zero; layer norms
    start at scale 1 and bias 0."""
    d, L = mc["hidden_size"], mc["num_hidden_layers"]
    H = mc["num_attention_heads"]
    hd, F = d // H, mc["ffn_dim"]
    vp = padded_vocab(mc["vocab_size"])
    return {
        "embed": ((vp, d), None, vocab_rows(mc["vocab_size"])),
        "pos_embed": ((mc["max_position_embeddings"], d), None),
        "final_norm/scale": ((d,), 1.0),
        "final_norm/bias": ((d,), 0.0),
        "layers/ln1/scale": ((L, d), 1.0),
        "layers/ln1/bias": ((L, d), 0.0),
        "layers/ln2/scale": ((L, d), 1.0),
        "layers/ln2/bias": ((L, d), 0.0),
        "layers/attn/wq": ((L, d, H, hd), None),
        "layers/attn/wk": ((L, d, H, hd), None),
        "layers/attn/wv": ((L, d, H, hd), None),
        "layers/attn/wo": ((L, H, hd, d), None),
        "layers/ffn/wu": ((L, d, F), None),
        "layers/ffn/wd": ((L, F, d), None),
    }


# ---------------------------------------------------------------------------
# the plain reference


def _ln(x, p, eps):
    mu = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), -1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + eps) * p["scale"] + p["bias"]


def _layer(x, lw, *, eps, control):
    lw = jax.tree.map(lambda a: a.astype(jnp.float32), lw)
    S = x.shape[0]
    H, hd = lw["attn"]["wq"].shape[1:]
    h = _ln(x, lw["ln1"], eps)
    # x (S, d) @ w (d, H, hd): activations scale per row, weights per (h, e)
    q = mm("sd,dhe->she", h, lw["attn"]["wq"], control, -1, 0)
    k = mm("sd,dhe->she", h, lw["attn"]["wk"], control, -1, 0)
    v = mm("sd,dhe->she", h, lw["attn"]["wv"], control, -1, 0)
    s = mm("she,the->hst", q * (hd ** -0.5), k, control, -1, -1)
    causal = jnp.arange(S)[:, None] >= jnp.arange(S)[None, :]
    s = jnp.where(causal[None], s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    o = mm("hst,the->she", p, v, control, -1, 0)
    x = x + mm("she,hed->sd", o, lw["attn"]["wo"], control, (-2, -1),
               (0, 1))
    h = _ln(x, lw["ln2"], eps)
    f = jax.nn.relu(mm("sd,df->sf", h, lw["ffn"]["wu"], control, -1, 0))
    return x + mm("sf,fd->sd", f, lw["ffn"]["wd"], control, -1, 0)


@functools.partial(jax.jit, static_argnames=("vocab", "eps", "control"))
def _logits(w, tokens, rows, *, vocab: int, eps: float, control: bool):
    S = tokens.shape[0]
    emb = w["embed"][:vocab].astype(jnp.float32)
    x = emb[tokens] + w["pos_embed"][:S].astype(jnp.float32)
    x, _ = jax.lax.scan(
        lambda x, lw: (_layer(x, lw, eps=eps, control=control), None),
        x, w["layers"])
    fn = jax.tree.map(lambda a: a.astype(jnp.float32), w["final_norm"])
    h = _ln(x[rows], fn, eps)
    return mm("rd,vd->rv", h, emb, control, -1, -1)


def logits_at(w, tokens, rows, *, mc: dict, control: bool):
    """Logits over the real vocabulary at positions ``rows`` of ``tokens``.

    tokens: (S,) int32, zero-padded past the sequence (causality keeps the
    real positions exact); rows: (R,) int32. Returns (R, vocab) float32.
    """
    return _logits(w, tokens, rows, vocab=mc["vocab_size"],
                   eps=mc["assumed"]["layer_norm_eps"], control=control)
