"""Seeded random weights, made on the device in the served dtype.

One jitted call draws every leaf from ``--seed``, straight into bfloat16,
in the stacked-layer layout the serving engine takes. The architecture's
module (``bench/arch/<model_type>.py``) names the leaves, their shapes and
their fills; the plain reference reads the same arrays: the benchmark
makes them, not the program. A leaf without a fill is drawn from
N(0, init_std^2).

Where the program's layout pads an axis (vocabulary rows past
``vocab_size``, query-head slots that hold no head), the module marks the
leaf's real entries along that axis, ``(shape, fill, (axis, real))``, and
the padded entries are drawn as zero: a plain reference over the padded
layout then computes the model exactly.
"""
from __future__ import annotations

import numpy as np

from bench import arch

VOCAB_MULTIPLE = 2048  # the engine pads the vocabulary to this multiple
HEAD_MULTIPLE = 16     # and the query heads to this many slots


def padded_vocab(vocab: int) -> int:
    return -(-vocab // VOCAB_MULTIPLE) * VOCAB_MULTIPLE


def vocab_rows(vocab: int) -> tuple:
    """The real rows of a (padded vocabulary, ...) leaf: ``(0, real)``."""
    return 0, np.arange(padded_vocab(vocab)) < vocab


def head_slots(n_heads: int, n_kv_heads: int) -> tuple:
    """The program's padded attention layout: (query slots, real query
    slots, KV slots, real KV slots), the masks as bool arrays.

    The query heads are padded to a multiple of ``HEAD_MULTIPLE`` slots.
    Under multi-head attention the KV heads are padded alike and the
    padding is the last slots. Under grouped attention each of the
    ``n_kv_heads`` groups holds slots / n_kv_heads query slots, the first
    n_heads / n_kv_heads of them real: query head h = k * r + j lies in
    slot k * group + j, and reads KV head k."""
    hp = -(-n_heads // HEAD_MULTIPLE) * HEAD_MULTIPLE
    if n_kv_heads == n_heads:
        real = np.arange(hp) < n_heads
        return hp, real, hp, real
    if hp % n_kv_heads or n_heads % n_kv_heads:
        raise ValueError(f"{n_heads} query heads over {n_kv_heads} KV "
                         f"heads fit no per-group layout of {hp} slots")
    group = hp // n_kv_heads
    real = np.arange(hp) % group < n_heads // n_kv_heads
    return hp, real, n_kv_heads, np.ones(n_kv_heads, bool)


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
    std = arch.dims(mc)["init_std"]

    def build(key):
        keys = jax.random.split(key, len(spec))
        flat = {}
        for k, (name, (shape, fill, *pad)) in zip(keys, spec.items()):
            if fill is not None:
                flat[name] = jnp.full(shape, fill, dt)
                continue
            w = jax.random.normal(k, shape, dt) * jnp.asarray(std, dt)
            if pad:  # padded entries stay zero
                axis, real = pad[0]
                w = jnp.where(np.asarray(real).reshape(
                    [-1 if a == axis else 1 for a in range(len(shape))]),
                    w, 0)
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
