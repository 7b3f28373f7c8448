"""Top-k Mixture-of-Experts family (Mixtral 8x22B, Phi-3.5-MoE).

Grouped one-hot dispatch (GSPMD-friendly Switch/GShard formulation): tokens
are split into groups of ~cfg.moe_group_size so the dispatch einsum stays a
few percent of expert compute; capacity = ceil(group·top_k·CF / E) with
priority-ordered slot assignment (k=0 routes before k=1).

Relufication (paper App. A): "MoE can be combined with relufication, having
sparsity inside FFN of each expert" — cfg.activation applies inside every
expert, and stage-2 post-norm ReLU sparsifies the router+expert input.
"""
from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.core import activations as acts
from repro.models import common as cm
from repro.models import serving_protocol as sp
from repro.models import transformer as T
from repro.sharding import rules

PyTree = Any


def init_moe(rng, cfg: ModelConfig, dtype) -> PyTree:
    d, F, E = cfg.d_model, cfg.d_ff, cfg.n_experts
    ks = jax.random.split(rng, 4)
    p = {
        "router": cm.dense_init(ks[0], (d, E), d, dtype),
        "wu": cm.dense_init(ks[1], (E, d, F), d, dtype),
        "wd": cm.dense_init(ks[2], (E, F, d), F, dtype),
    }
    if cfg.ffn_kind == "glu":
        p["wg"] = cm.dense_init(ks[3], (E, d, F), d, dtype)
    return p


def _route(p, x, cfg: ModelConfig, stats: cm.StatsCollector):
    """Top-k routing + grouped priority slot assignment for t flat tokens.

    Returns (xg (G, tg, d), dispatch (G, tg, E, cap) bool, combine
    (G, tg, E, cap) f32, (G, tg, cap)). Shared verbatim by the training /
    legacy path (``apply_moe``) and the paged serving path
    (``apply_moe_window``) so both route bit-identically. Under drop-free
    capacity (cap >= tg·top_k, i.e. capacity_factor >= n_experts) every
    token's experts get slots regardless of which other tokens share the
    batch — each slot's value is an EXACT copy of one token's row — which
    is what makes the serving path's different batch shapes byte-identical
    to the sequential legacy decode."""
    t, d = x.shape
    E, k = cfg.n_experts, cfg.top_k

    G = max(1, t // cfg.moe_group_size)
    while t % G:
        G -= 1
    tg = t // G
    cap = max(1, int(-(-tg * k * cfg.capacity_factor // E)))

    xg = x.reshape(G, tg, d)
    logits = jnp.einsum("gtd,de->gte", xg, p["router"]).astype(jnp.float32)
    gates_all = jax.nn.softmax(logits, axis=-1)
    topv, topi = jax.lax.top_k(gates_all, k)  # (G, tg, k)
    topv = topv / jnp.sum(topv, axis=-1, keepdims=True)  # renormalize (mixtral)

    # priority slot assignment: k=0 claims capacity first
    dispatch = jnp.zeros((G, tg, E, cap), jnp.bool_)
    combine = jnp.zeros((G, tg, E, cap), jnp.float32)
    counts = jnp.zeros((G, E), jnp.int32)
    for kk in range(k):
        oh = jax.nn.one_hot(topi[..., kk], E, dtype=jnp.int32)  # (G, tg, E)
        pos = jnp.cumsum(oh, axis=1) - oh + counts[:, None, :]  # slot index
        ok = (pos < cap) & (oh > 0)
        slot = jax.nn.one_hot(jnp.where(ok, pos, cap), cap + 1,
                              dtype=jnp.float32)[..., :cap]  # (G, tg, E, cap)
        sel = slot * oh[..., None]
        dispatch = dispatch | (sel > 0)
        combine = combine + sel * topv[..., kk][..., None, None]
        counts = counts + jnp.sum(oh, axis=1)
    stats.add("moe_drop_frac", 1.0 - jnp.sum(dispatch) / (G * tg * k))
    stats.add("moe_load_cv", jnp.std(jnp.sum(combine, (1, 3)))
              / (jnp.mean(jnp.sum(combine, (1, 3))) + 1e-9))
    return xg, dispatch, combine, (G, tg, cap)


def apply_moe(p, x, cfg: ModelConfig, *, stats: cm.StatsCollector,
              decode: bool = False):
    """x: (tokens, d) -> (tokens, d). Top-k routing with capacity."""
    act = acts.get(cfg.activation, shift=cfg.sparsity.shift)
    xg, dispatch, combine, _ = _route(p, x, cfg, stats)

    dd = dispatch.astype(x.dtype)
    xe = rules.constrain(jnp.einsum("gtec,gtd->gecd", dd, xg),
                         "dp", None, None, None)
    if cfg.ffn_kind == "glu":
        pre = jnp.einsum("gecd,edf->gecf", xe, p["wg"])
        stats.add_preact("moe_pre", pre)
        h = act(pre) * jnp.einsum("gecd,edf->gecf", xe, p["wu"])
    else:
        pre = jnp.einsum("gecd,edf->gecf", xe, p["wu"])
        stats.add_preact("moe_pre", pre)
        h = act(pre)
    stats.add_sparsity("down_in", h)
    h = rules.constrain(h, "dp", None, None, "model")
    ye = jnp.einsum("gecf,efd->gecd", h, p["wd"])
    # pin the output to token-parallel: GSPMD otherwise resolves the dp-axis
    # collision (groups vs wd's d_model FSDP dim) by replicating the einsum
    ye = rules.constrain(ye, "dp", None, None, None)
    y = jnp.einsum("gtec,gecd->gtd", combine.astype(x.dtype), ye)
    return y.reshape(x.shape)


def init_block(rng, cfg: ModelConfig, dtype) -> PyTree:
    ks = jax.random.split(rng, 2)
    return {
        "ln1": cm.init_norm(cfg, cfg.d_model, dtype),
        "attn": T.init_attn(ks[0], cfg, dtype),
        "ln2": cm.init_norm(cfg, cfg.d_model, dtype),
        "moe": init_moe(ks[1], cfg, dtype),
    }


def apply_block(p, x, cfg: ModelConfig, *, positions, stats, return_kv=False):
    h = T.post_norm(cm.apply_norm(p["ln1"], x, cfg), cfg)
    if return_kv:
        a, kv = T.apply_attn_full(p["attn"], h, cfg, positions=positions,
                                  stats=stats, return_kv=True)
    else:
        a = T.apply_attn_full(p["attn"], h, cfg, positions=positions, stats=stats)
    x = x + a
    h = T.post_norm(cm.apply_norm(p["ln2"], x, cfg), cfg)
    b, s, d = h.shape
    f = apply_moe(p["moe"], h.reshape(b * s, d), cfg, stats=stats).reshape(b, s, d)
    x = x + f
    return (x, kv) if return_kv else x


def apply_block_decode(p, x, cfg, k_cache, v_cache, pos, *, stats, layer=None):
    h = T.post_norm(cm.apply_norm(p["ln1"], x[:, None], cfg)[:, 0], cfg)
    a, k_cache, v_cache = T.apply_attn_decode(
        p["attn"], h, cfg, k_cache, v_cache, pos, stats=stats, layer=layer)
    x = x + a
    h = T.post_norm(cm.apply_norm(p["ln2"], x[:, None], cfg)[:, 0], cfg)
    x = x + apply_moe(p["moe"], h, cfg, stats=stats, decode=True)
    return x, k_cache, v_cache


# ---------------------------------------------------------------------------
# family interface (reuses the dense scaffolding with our block fns)


def init_params(rng, cfg: ModelConfig):
    dtype = jnp.dtype(cfg.param_dtype)
    vp = cm.padded_vocab(cfg.vocab_size)
    ks = jax.random.split(rng, 4)
    layer_keys = jax.random.split(ks[0], cfg.n_layers)
    layers = jax.vmap(lambda k: init_block(k, cfg, dtype))(layer_keys)
    p = {"embed": cm.embed_init(ks[1], (vp, cfg.d_model), dtype),
         "layers": layers,
         "final_norm": cm.init_norm(cfg, cfg.d_model, dtype)}
    if not cfg.tie_embeddings:
        p["unembed"] = cm.embed_init(ks[2], (vp, cfg.d_model), dtype)
    if not cfg.use_rope:
        p["pos_embed"] = cm.embed_init(ks[3], (cfg.max_seq_len, cfg.d_model), dtype)
    return p


def model_forward(params, batch, cfg: ModelConfig, *, stats=None,
                  remat_policy="none"):
    return T.forward(params, batch["tokens"], cfg, stats=stats,
                     remat_block=cm.wrap_block(remat_policy, apply_block))


def init_cache(cfg: ModelConfig, batch: int, max_len: int):
    return T.init_cache(cfg, batch, max_len)


def model_prefill(params, batch, cfg: ModelConfig, max_len: int, stats=None):
    stats = stats or cm.StatsCollector(False)
    logits, kv = T.forward(params, batch["tokens"], cfg, stats=stats,
                           return_kv=True, remat_block=apply_block)
    return logits[:, -1], T.finalize_prefill_cache(*kv, max_len)


def model_decode(params, cache, token, pos, cfg: ModelConfig, stats=None):
    stats = stats or cm.StatsCollector(False)
    params = cm.cast_params(params, cfg)
    x = T.embed_tokens(params, token[:, None], cfg, pos[:, None])[:, 0]

    if stats.active:
        kc, vc = cache["k"], cache["v"]
        for i in range(cfg.n_layers):
            pl_i = jax.tree.map(lambda a: a[i], params["layers"])
            x, kc, vc = apply_block_decode(pl_i, x, cfg, kc, vc, pos,
                                           stats=stats, layer=i)
        new_cache = {"k": kc, "v": vc}
    else:
        def body(carry, xs):
            x, kc, vc = carry
            pl_i, li = xs
            x, kc, vc = apply_block_decode(pl_i, x, cfg, kc, vc, pos,
                                           stats=stats, layer=li)
            return (x, kc, vc), None
        (x, kc, vc), _ = jax.lax.scan(
            body, (x, cache["k"], cache["v"]),
            (params["layers"], jnp.arange(cfg.n_layers)))
        new_cache = {"k": kc, "v": vc}

    x = cm.apply_norm(params["final_norm"], x[:, None], cfg)[:, 0]
    return T.logits_from(params, x, cfg), new_cache


# ---------------------------------------------------------------------------
# continuous-batching serving: the full paged interface (serving_protocol
# caps: paged_decode + chunked_prefill + spec_verify)
#
# Router top-k IS structured activation sparsity at expert granularity: a
# token reads top_k/n_experts of the FFN weights before any within-expert
# γ-masking applies, so the serving density telemetry composes both layers
# (density = expert fraction × within-expert eff density) and the engine's
# ``weight_io_bytes_per_step`` — density × dense-ALL-experts bytes — reports
# activated-expert bytes.
#
# Exactness: all serving configs use drop-free capacity (capacity_factor >=
# n_experts ⇒ cap >= tg·top_k). Then per-token routing results do not depend
# on co-batched tokens (each expert slot is an exact copy of one token's
# row; extra slots only add exact zeros / ×1.0 terms), so the engine's
# slot-batched, scratch-padded windows are byte-identical at f32 to the
# legacy sequential ``model_decode`` — the same invariance that makes
# chunked prefill's zero-padded windows safe. With droppable capacity the
# paths stay correct but dropped tokens may differ between batch shapes.


def apply_moe_window(p, x, cfg: ModelConfig, *, mask, refresh, valid):
    """Decode MoE-FFN over a W-token window with per-request γ-window reuse,
    batched over slots. x: (b, W, d); mask: (b, F) bool γ-window rows;
    refresh: (b,); valid: (b, W) real window tokens.

    Returns (out (b, W, d),
             act (b, F) union within-expert activity over valid tokens,
             scores (b, F//tile) window-union tile activity,
             density (b,) mean per-token fraction of expert FFN weights
                 read = routed-expert fraction × within-expert eff density,
             union_density (b,) fraction of the (E, F) expert-unit grid in
                 the window's read union = 1 − s_agg at expert granularity).
    """
    act_fn = acts.get(cfg.activation, shift=cfg.sparsity.shift)
    b, W, d = x.shape
    E, F = cfg.n_experts, cfg.d_ff
    stats = cm.StatsCollector(False)
    xg, dispatch, combine, (G, tg, cap) = _route(p, x.reshape(b * W, d),
                                                 cfg, stats)

    dd = dispatch.astype(x.dtype)
    # serve mesh: expert dim over "model" (sharding/rules.py serve map) —
    # each device computes its experts' slots; identity without a mesh
    xe = rules.constrain(jnp.einsum("gtec,gtd->gecd", dd, xg),
                         None, "model", None, None)
    if cfg.ffn_kind == "glu":
        h = act_fn(jnp.einsum("gecd,edf->gecf", xe, p["wg"])) \
            * jnp.einsum("gecd,edf->gecf", xe, p["wu"])
    else:
        h = act_fn(jnp.einsum("gecd,edf->gecf", xe, p["wu"]))
    h = rules.constrain(h, None, "model", None, None)

    # γ-window gate, dispatched to expert-slot space: each slot's eff row is
    # an exact copy of its token's slot-level eff (drop-free), so gating
    # here equals gating per token — and is ×1.0 (bit-exact) under refresh
    eff = mask | refresh[:, None]  # (b, F)
    eff_tok = jnp.broadcast_to(eff[:, None, :], (b, W, F)).reshape(G, tg, F)
    eff_slots = jnp.einsum("gtec,gtf->gecf", dd, eff_tok.astype(h.dtype))
    h = h * eff_slots

    ye = jnp.einsum("gecf,efd->gecd", h, p["wd"])
    y = jnp.einsum("gtec,gecd->gtd", combine.astype(x.dtype), ye)
    out = y.reshape(b, W, d)

    # telemetry: per-token within-expert activity unioned over the token's
    # routed experts (the slot-level γ-mask is shared across experts)
    hq = (h != 0).astype(jnp.float32)  # (G, E, cap, F)
    act_tok = (jnp.einsum("gtec,gecf->gtf", dd.astype(jnp.float32), hq)
               .reshape(b, W, F) > 0)
    act_tok = act_tok & valid[:, :, None]
    act = jnp.any(act_tok, axis=1)  # (b, F)
    from repro.kernels.fused_ffn import window_tile_activity
    scores = window_tile_activity(act_tok.astype(jnp.float32),
                                  cm.ffn_gather_tile(cfg))

    texp = jnp.any(dispatch, axis=3).reshape(b, W, E)  # token's experts
    efrac = jnp.mean(texp.astype(jnp.float32), -1)  # (b, W)
    tok_density = efrac * jnp.mean(eff.astype(jnp.float32), -1)[:, None]
    vf = valid.astype(jnp.float32)
    denom = jnp.maximum(jnp.sum(vf, 1), 1.0)
    density = jnp.sum(tok_density * vf, 1) / denom  # (b,)

    read = (texp[:, :, :, None] & eff[:, None, None, :]
            & valid[:, :, None, None])  # (b, W, E, F)
    union_density = jnp.mean(jnp.any(read, 1).astype(jnp.float32), (1, 2))
    return out, act, scores, density, union_density


def apply_block_window_paged(p, x, cfg: ModelConfig, k_pages, v_pages, table,
                             pos, valid, *, layer, block_size: int, mask,
                             refresh):
    stats = cm.StatsCollector(False)
    h = T.post_norm(cm.apply_norm(p["ln1"], x, cfg), cfg)
    a, k_pages, v_pages = T.apply_attn_window_paged(
        p["attn"], h, cfg, k_pages, v_pages, table, pos, valid, layer=layer,
        block_size=block_size, stats=stats)
    x = x + a
    h = T.post_norm(cm.apply_norm(p["ln2"], x, cfg), cfg)
    f, act, scores, density, udens = apply_moe_window(
        p["moe"], h, cfg, mask=mask, refresh=refresh, valid=valid)
    x = x + f
    return x, k_pages, v_pages, act, scores, density, udens


def init_paged_cache(cfg: ModelConfig, n_blocks: int, block_size: int,
                     sharding=None, head_dim=None):
    return cm.init_paged_cache(cfg, n_blocks, block_size, sharding=sharding,
                               head_dim=head_dim)


def model_prefill_paged(params, batch, cfg: ModelConfig, pages, blocks,
                        block_size: int, true_len=None):
    """Whole-prompt prefill into freshly allocated pool blocks (the dense
    family's contract; see transformer.prefill_paged). Zero-padding to a
    block multiple is routing-safe under drop-free capacity (module note)."""
    li = None if true_len is None else true_len - 1
    logits, kv = T.forward(params, batch["tokens"], cfg, return_kv=True,
                           last_index=li, remat_block=apply_block)
    k, v = kv  # (L, 1, s, kvp, hd)
    kp = cm.paged_write_prefill(pages["k"], k[:, 0], blocks, block_size)
    vp = cm.paged_write_prefill(pages["v"], v[:, 0], blocks, block_size)
    return logits[:, -1], {"k": kp, "v": vp}


def model_verify_window_paged(params, pages, table, tokens, pos0, wlen,
                              cfg: ModelConfig, ffn_masks, refresh,
                              block_size: int, fast_kernels: bool = False):
    """W-token window per slot over the shared page pool — the speculative
    verification target step, MoE edition (same contract as
    transformer.verify_window_paged; aux density/union_density measure the
    EXPERT-weighted fractions). fast_kernels is accepted for interface
    parity but MoE uses the documented XLA dispatch fallback
    (kernels/fused_decode.py module note)."""
    del fast_kernels

    def layer_fn(pl_i, li, x, kp, vp, fm, pos, valid):
        x, kp, vp, act, scores, density, udens = apply_block_window_paged(
            pl_i, x, cfg, kp, vp, table, pos, valid, layer=li,
            block_size=block_size, mask=fm, refresh=refresh)
        return x, kp, vp, (act, scores, density, udens)

    return sp.window_step_core(params, pages, tokens, pos0, wlen, cfg,
                               ffn_masks, refresh, layer_fn=layer_fn,
                               embed_fn=T.embed_tokens,
                               logits_fn=T.logits_from)


def model_prefill_chunk_paged(params, batch, cfg: ModelConfig, pages, table,
                              pos0, clen, ffn_masks, refresh,
                              block_size: int, fast_kernels: bool = False):
    """One fixed-shape prefill chunk IS a window step (the dense family's
    delegation, transformer.prefill_chunk_paged): chunk tokens write K/V at
    their own positions, tokens past clen scratch-route, and the window's
    union activity seeds the warm γ-mask."""
    return model_verify_window_paged(params, pages, table, batch["tokens"],
                                     pos0, clen, cfg, ffn_masks, refresh,
                                     block_size, fast_kernels=fast_kernels)


def model_decode_paged(params, pages, table, token, pos, cfg: ModelConfig,
                       ffn_masks, refresh, block_size: int,
                       fast_kernels: bool = False):
    """Plain continuous-batching decode = the W == 1 window step. Unlike the
    dense family (whose decode keeps a hand-specialized bf16-frozen
    lowering), MoE serves at f32-pinned exactness from day one, so the
    window path with wlen == 1 IS the decode step — aux drops the window's
    union_density and, running no fused FFN kernel, reports no tiles
    fetched, to match the engine's decode contract."""
    logits, pages, new_masks, (act, scores, density, _udens) = \
        model_verify_window_paged(params, pages, table, token[:, None], pos,
                                  jnp.ones_like(pos), cfg, ffn_masks,
                                  refresh, block_size,
                                  fast_kernels=fast_kernels)
    ffn_tiles = sp.no_ffn_tiles(token.shape[0], cfg.n_layers)
    return logits[:, 0], pages, new_masks, (act, scores, density, ffn_tiles)
