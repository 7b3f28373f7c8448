"""Percent of paged_window_attention's device time that its work needs at
the chip's peaks: each decoding slot's valid context read once."""
from bench import readers


def read(ctx):
    mc, count = ctx.mc, ctx.counts("paged_window_attention")
    H = mc["num_attention_heads"]

    def work(step):
        return count.work(tokens=step.n_decode,
                          context_tokens=step.context_tokens, n_heads=H,
                          head_dim=mc["hidden_size"] // H)
    return readers.roofline(ctx, "paged_window_attention", work)
