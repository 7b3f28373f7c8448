"""Work of one ``fused_sparse_ffn`` call (kernels/fused_decode.py).

A call runs one layer's FFN for a batch of decode tokens over per-token
lists of named d_ff tiles. The work is each token's up and down products
over the tiles it names, and reading every tile that any token names
ONCE, with the tokens' activations in and out. What the kernel as written
reads (each token's tiles again, ``fused_decode.modeled_weight_bytes``) is
not the work: a kernel that reads a tile once per call is the bound.
"""
from __future__ import annotations


def work(*, tokens: int, d_model: int, tile: int, tiles_named: int,
         tile_token_pairs: int, n_proj: int = 2, itemsize: int = 2):
    """tokens: decode tokens in the call; tiles_named: distinct tiles any
    token names; tile_token_pairs: (token, named tile) pairs; n_proj:
    projections through a tile (2 for a ReLU MLP: up and down; 3 for
    a gated FFN: gate, up and down)."""
    flops = 2.0 * tile_token_pairs * n_proj * tile * d_model
    weights = tiles_named * n_proj * tile * d_model * itemsize
    activations = 2 * tokens * d_model * itemsize
    return flops, float(weights + activations)
