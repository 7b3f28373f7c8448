"""Model FLOPs of the work a serving step does, from the configuration's
sizes. The per-token counts follow the program's ``core/flops.py``
(``macs_per_token``, ``_attn_macs``) for a dense decoder, copied here so
that the yardstick stays fixed whatever the program's copy becomes.
"""
from __future__ import annotations


def weight_macs(mc: dict) -> float:
    """Non-embedding weight MACs of one token through every layer."""
    d, F = mc["hidden_size"], mc["ffn_dim"]
    attn = 4 * d * d            # q, k, v and out projections (H * hd = d)
    return mc["num_hidden_layers"] * (attn + 2 * d * F)


def attn_macs(mc: dict, context: float) -> float:
    """Score and value MACs of one query over ``context`` keys, all layers."""
    return mc["num_hidden_layers"] * 2.0 * mc["hidden_size"] * context


def decode_flops(mc: dict, n_tokens: int, context_tokens: int) -> float:
    """One decode step of ``n_tokens`` slots whose attention spans
    ``context_tokens`` keys in all, each with its logits."""
    per = weight_macs(mc) + mc["hidden_size"] * mc["vocab_size"]
    return 2.0 * (n_tokens * per + attn_macs(mc, context_tokens))


def prefill_flops(mc: dict, prompt_len: int) -> float:
    """A whole-prompt prefill: every position through the layers with
    causal attention, and logits at the last position only."""
    s = prompt_len
    return 2.0 * (s * weight_macs(mc) + attn_macs(mc, s * (s + 1) / 2)
                  + mc["hidden_size"] * mc["vocab_size"])
