"""Seeded random OPT weights, made on the device in the served dtype.

One jitted call draws every leaf from ``--seed``, straight into bfloat16,
in the stacked-layer layout the serving engine takes. The plain reference
(``bench/reference.py``) reads the same arrays: the benchmark makes them,
not the program. Scales follow OPT's own initialisation: every projection
and embedding N(0, init_std^2), layer-norm scale 1 and bias 0. OPT's linear
biases are zero at initialisation, and the program has none, so neither
side carries them.
"""
from __future__ import annotations

VOCAB_MULTIPLE = 2048  # the engine pads the vocabulary to this multiple


def shapes(mc: dict) -> dict:
    """Leaf name -> (shape, fill): fill None draws N(0, init_std^2)."""
    d, L = mc["hidden_size"], mc["num_hidden_layers"]
    H = mc["num_attention_heads"]
    hd, F = d // H, mc["ffn_dim"]
    vp = -(-mc["vocab_size"] // VOCAB_MULTIPLE) * VOCAB_MULTIPLE
    return {
        "embed": ((vp, d), None),
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


def _nest(flat: dict) -> dict:
    out: dict = {}
    for path, v in flat.items():
        node = out
        *head, last = path.split("/")
        for k in head:
            node = node.setdefault(k, {})
        node[last] = v
    return out


def make(mc: dict, seed: int, dtype: str = "bfloat16"):
    """The whole weight tree, from one jitted call on the default device."""
    import jax
    import jax.numpy as jnp

    spec = shapes(mc)
    dt = jnp.dtype(dtype)
    vocab, std = mc["vocab_size"], mc["init_std"]

    def build(key):
        keys = jax.random.split(key, len(spec))
        flat = {}
        for k, (name, (shape, fill)) in zip(keys, spec.items()):
            if fill is not None:
                flat[name] = jnp.full(shape, fill, dt)
                continue
            w = jax.random.normal(k, shape, dt) * jnp.asarray(std, dt)
            if name == "embed":  # padded vocabulary rows stay zero
                w = jnp.where(jnp.arange(shape[0])[:, None] < vocab, w, 0)
            flat[name] = w
        return _nest(flat)

    return jax.jit(build)(rng_key(seed))


def rng_key(seed: int):
    """A PRNG key that keeps every bit of a seed wider than 32 bits."""
    import jax
    key = jax.random.key(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, (seed >> 32) & 0xFFFFFFFF)


def nbytes(tree) -> int:
    import jax
    return sum(x.nbytes for x in jax.tree.leaves(tree))
