"""Registry adapter for the dense decoder family (batch-dict interface)."""
from __future__ import annotations

from repro.configs.base import ModelConfig
from repro.models import common as cm
from repro.models import transformer as T


def init_params(rng, cfg: ModelConfig):
    return T.init_params(rng, cfg)


def model_forward(params, batch, cfg: ModelConfig, *, stats=None,
                  remat_policy="none"):
    return T.forward(params, batch["tokens"], cfg, stats=stats,
                     remat_block=cm.wrap_block(remat_policy, T.apply_block))


def init_cache(cfg: ModelConfig, batch: int, max_len: int):
    return T.init_cache(cfg, batch, max_len)


def model_prefill(params, batch, cfg: ModelConfig, max_len: int, stats=None):
    return T.prefill(params, batch["tokens"], cfg, max_len, stats=stats)


def model_decode(params, cache, token, pos, cfg: ModelConfig, stats=None,
                 ffn_masks=None):
    return T.decode_step(params, cache, token, pos, cfg, stats=stats,
                         ffn_masks=ffn_masks)


# -- continuous-batching (paged-cache) serving interface --------------------

def init_paged_cache(cfg: ModelConfig, n_blocks: int, block_size: int,
                     sharding=None, head_dim=None):
    return cm.init_paged_cache(cfg, n_blocks, block_size, sharding=sharding,
                               head_dim=head_dim)


def model_prefill_paged(params, batch, cfg: ModelConfig, pages, blocks,
                        block_size: int, true_len=None):
    return T.prefill_paged(params, batch["tokens"], cfg, pages, blocks,
                           block_size=block_size, true_len=true_len)


def model_prefill_chunk_paged(params, batch, cfg: ModelConfig, pages, table,
                              pos0, clen, ffn_masks, refresh,
                              block_size: int, fast_kernels: bool = False):
    return T.prefill_chunk_paged(params, pages, table, batch["tokens"],
                                 pos0, clen, cfg, ffn_masks, refresh,
                                 block_size=block_size,
                                 fast_kernels=fast_kernels)


def model_decode_paged(params, pages, table, token, pos, cfg: ModelConfig,
                       ffn_masks, refresh, block_size: int,
                       fast_kernels: bool = False):
    return T.decode_step_paged(params, pages, table, token, pos, cfg,
                               ffn_masks, refresh, block_size=block_size,
                               fast_kernels=fast_kernels)


def model_decode_paged_predicted(params, pages, table, token, pos,
                                 cfg: ModelConfig, ffn_masks, refresh,
                                 pred_params, kind: str, tile: int,
                                 k_tiles: int, block_size: int,
                                 measure: bool = True, shards: int = 1,
                                 fast_kernels: bool = False):
    return T.decode_step_paged_predicted(params, pages, table, token, pos,
                                         cfg, ffn_masks, refresh, pred_params,
                                         kind=kind, tile=tile,
                                         k_tiles=k_tiles,
                                         block_size=block_size,
                                         measure=measure, shards=shards,
                                         fast_kernels=fast_kernels)


def model_verify_window_paged(params, pages, table, tokens, pos0, wlen,
                              cfg: ModelConfig, ffn_masks, refresh,
                              block_size: int, fast_kernels: bool = False):
    return T.verify_window_paged(params, pages, table, tokens, pos0, wlen,
                                 cfg, ffn_masks, refresh,
                                 block_size=block_size,
                                 fast_kernels=fast_kernels)


def model_draft_gamma_paged(params, pages, table, token, pos0, wlen,
                            cfg: ModelConfig, gamma: int, block_size: int,
                            next_fn=None, fast_kernels: bool = False):
    return T.draft_gamma_paged(params, pages, table, token, pos0, wlen, cfg,
                               gamma=gamma, block_size=block_size,
                               next_fn=next_fn,
                               fast_kernels=fast_kernels)
