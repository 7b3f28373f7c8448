"""Percent of paged_window_attention's device time that its work needs at
the chip's peaks: one call per layer, each decoding slot's valid context
read once (at most the layer's window), K and V of the model's KV heads."""
from bench import flops, readers


def read(ctx):
    dm, count = ctx.dims, ctx.counts("paged_window_attention")

    def calls(step):
        return [count.work(tokens=step.n_decode, context_tokens=keys,
                           n_heads=dm["n_heads"], head_dim=dm["head_dim"],
                           n_kv_heads=dm["n_kv_heads"])
                for keys in flops.keys_per_layer(ctx.mc, step.contexts)]
    return readers.roofline(ctx, "paged_window_attention", calls)
