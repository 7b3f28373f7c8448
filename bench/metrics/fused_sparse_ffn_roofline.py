"""Percent of fused_sparse_ffn's device time that its work needs at the
chip's peaks. The cells' requests refresh their tile lists every token
(no reuse window), so every decoding token names every tile: the work of
each call is the dense FFN of its layer read once, and each token's
products through each of its projections."""
from bench import readers


def read(ctx):
    dm, count = ctx.dims, ctx.counts("fused_sparse_ffn")
    tile = 128
    n_tiles = dm["d_ff"] // tile

    def calls(step):
        work = count.work(tokens=step.n_decode, d_model=dm["d_model"],
                          tile=tile, tiles_named=n_tiles,
                          tile_token_pairs=step.n_decode * n_tiles,
                          n_proj=dm["n_proj"])
        return [work] * dm["calls"]["fused_sparse_ffn"]
    return readers.roofline(ctx, "fused_sparse_ffn", calls)
