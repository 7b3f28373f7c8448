"""Work of one ``paged_window_attention`` call (kernels/paged_attention.py).

A decode call attends every slot's one query to its valid context: the
K and V of each valid position read once, q in and the output out. Blocks
past a slot's length, and idle slots, are not work. Under grouped or
multi-query attention K and V hold ``n_kv_heads`` heads, which every query
head of their group reads.
"""
from __future__ import annotations


def work(*, tokens: int, context_tokens: int, n_heads: int, head_dim: int,
         n_kv_heads=None, itemsize: int = 2):
    """tokens: slots decoding in the call; context_tokens: their valid
    context positions summed (the current token included); n_kv_heads:
    K/V heads, ``n_heads`` when left out."""
    kv_heads = n_heads if n_kv_heads is None else n_kv_heads
    flops = 2.0 * 2 * context_tokens * n_heads * head_dim
    kv = 2 * context_tokens * kv_heads * head_dim * itemsize
    qo = 2 * tokens * n_heads * head_dim * itemsize
    return flops, float(kv + qo)
