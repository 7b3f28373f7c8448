"""Model FLOPs of the work a serving step does, from the sizes that the
architecture's module gives (``dims``), following the program's
``core/flops.py``, copied so that the yardstick stays fixed whatever the
program's copy becomes. Every decoder block counts alike: query and output
projections of ``n_heads`` heads, key and value projections of
``n_kv_heads``, ``n_proj`` FFN projections, attention over the query
heads, and logits at each decoded position.
"""
from __future__ import annotations

from bench import arch


def weight_macs(mc: dict) -> float:
    """Non-embedding weight MACs of one token through every layer."""
    dm = arch.dims(mc)
    d, hd = dm["d_model"], dm["head_dim"]
    attn = 2 * d * dm["n_heads"] * hd + 2 * d * dm["n_kv_heads"] * hd
    return dm["n_layers"] * (attn + dm["n_proj"] * d * dm["d_ff"])


def attn_macs(mc: dict, context: float) -> float:
    """Score and value MACs of one query over ``context`` keys, all layers."""
    dm = arch.dims(mc)
    return dm["n_layers"] * 2.0 * dm["n_heads"] * dm["head_dim"] * context


def _head_macs(mc: dict) -> float:
    dm = arch.dims(mc)
    return dm["d_model"] * dm["vocab_size"]


def decode_flops(mc: dict, n_tokens: int, context_tokens: int) -> float:
    """One decode step of ``n_tokens`` slots whose attention spans
    ``context_tokens`` keys in all, each with its logits."""
    per = weight_macs(mc) + _head_macs(mc)
    return 2.0 * (n_tokens * per + attn_macs(mc, context_tokens))


def prefill_flops(mc: dict, prompt_len: int) -> float:
    """A whole-prompt prefill: every position through the layers with
    causal attention, and logits at the last position only."""
    s = prompt_len
    return 2.0 * (s * weight_macs(mc) + attn_macs(mc, s * (s + 1) / 2)
                  + _head_macs(mc))
