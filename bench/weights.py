"""Seeded random weights, made on the device in the served dtype.

One jitted call draws every leaf from ``--seed``, straight into bfloat16,
in the stacked-layer layout the serving engine takes. The architecture's
module (``bench/arch/<model_type>.py``) names the leaves, their shapes and
their fills; the plain reference reads the same arrays: the benchmark
makes them, not the program. A leaf without a fill is drawn from
N(0, init_std^2).
"""
from __future__ import annotations

from bench import arch

VOCAB_MULTIPLE = 2048  # the engine pads the vocabulary to this multiple


def padded_vocab(vocab: int) -> int:
    return -(-vocab // VOCAB_MULTIPLE) * VOCAB_MULTIPLE


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

    spec = arch.load(mc).shapes(mc)
    dt = jnp.dtype(dtype)
    dm = arch.dims(mc)
    vocab, std = dm["vocab_size"], dm["init_std"]

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
