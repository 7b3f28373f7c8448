"""Model FLOPs of the work a serving step does, from the sizes that the
architecture's module gives (``dims``), following the program's
``core/flops.py``, copied so that the yardstick stays fixed whatever the
program's copy becomes. Every decoder block counts alike: query and output
projections of ``n_heads`` heads, key and value projections of
``n_kv_heads``, ``n_proj`` FFN projections, attention over the query
heads, and logits at each decoded position.

A layer whose ``dims`` entry in ``windows`` is W > 0 attends each query
to its last W keys at most; 0, or no ``windows`` at all, is full attention.
"""
from __future__ import annotations

from typing import List, Sequence

from bench import arch


def windows(mc: dict) -> List[int]:
    """Each layer's attention window, 0 for full attention."""
    dm = arch.dims(mc)
    w = list(dm.get("windows") or [0] * dm["n_layers"])
    if len(w) != dm["n_layers"]:
        raise ValueError(f"{len(w)} windows for {dm['n_layers']} layers")
    return w


def weight_macs(mc: dict) -> float:
    """Non-embedding weight MACs of one token through every layer."""
    dm = arch.dims(mc)
    d, hd = dm["d_model"], dm["head_dim"]
    attn = 2 * d * dm["n_heads"] * hd + 2 * d * dm["n_kv_heads"] * hd
    return dm["n_layers"] * (attn + dm["n_proj"] * d * dm["d_ff"])


def keys_per_layer(mc: dict, contexts: Sequence[int]) -> List[int]:
    """Keys that queries of these contexts (one each, current token
    included) read in each layer: a windowed layer reads at most W each."""
    return [sum(min(c, w) for c in contexts) if w else sum(contexts)
            for w in windows(mc)]


def attn_macs(mc: dict, contexts: Sequence[int]) -> float:
    """Score and value MACs over every layer of one query per context."""
    dm = arch.dims(mc)
    return 2.0 * dm["n_heads"] * dm["head_dim"] * sum(
        keys_per_layer(mc, contexts))


def _prefix_keys(s: int, w: int) -> float:
    """Keys that the positions of an s-token prompt read under causal
    attention in a layer of window w: the sum over i < s of min(i + 1, w)."""
    if not w or w >= s:
        return s * (s + 1) / 2
    return w * (w + 1) / 2 + (s - w) * w


def _head_macs(mc: dict) -> float:
    dm = arch.dims(mc)
    return dm["d_model"] * dm["vocab_size"]


def decode_flops(mc: dict, contexts: Sequence[int]) -> float:
    """One decode step: a token of each decoding slot, whose attention
    spans that slot's context, each with its logits."""
    per = weight_macs(mc) + _head_macs(mc)
    return 2.0 * (len(contexts) * per + attn_macs(mc, contexts))


def prefill_flops(mc: dict, prompt_len: int) -> float:
    """A whole-prompt prefill: every position through the layers with
    causal attention, and logits at the last position only."""
    s = prompt_len
    dm = arch.dims(mc)
    keys = sum(_prefix_keys(s, w) for w in windows(mc))
    return 2.0 * (s * weight_macs(mc)
                  + 2.0 * dm["n_heads"] * dm["head_dim"] * keys
                  + _head_macs(mc))
