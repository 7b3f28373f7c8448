"""Percent of paged_window_attention's device time that its work needs at
the chip's peaks: each decoding slot's valid context read once, K and V
of the model's KV heads."""
from bench import readers


def read(ctx):
    dm, count = ctx.dims, ctx.counts("paged_window_attention")

    def work(step):
        return count.work(tokens=step.n_decode,
                          context_tokens=step.context_tokens,
                          n_heads=dm["n_heads"], head_dim=dm["head_dim"],
                          n_kv_heads=dm["n_kv_heads"])
    return readers.roofline(ctx, "paged_window_attention", work)
