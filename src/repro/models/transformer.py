"""Dense decoder-only transformer family.

Covers: OPT (layernorm, learned positions, ReLU-MLP), Llama/DeepSeek (rmsnorm,
RoPE, SwiGLU), Falcon (layernorm, GELU-MLP), Qwen2 (QKV bias), Qwen3
(qk-norm), StarCoder2 (GQA+GELU), and the InternLM2 backbone of InternVL2.

Layers are stacked on a leading axis and iterated with lax.scan so the HLO is
one layer body regardless of depth (95-layer deepseek compiles as fast as a
2-layer toy). The attention / FFN builders here are reused by moe.py,
hybrid.py and encdec.py.

Relufication hooks (paper Sec. 4):
  * stage 1 = cfg.activation == "relu" (or "shifted_relu")
  * stage 2 = cfg.post_norm_relu: ReLU is applied to the output of each
    pre-attention / pre-FFN norm, sparsifying QKV and up-projection inputs.
Sparse decode (paper Sec. 4.2/5, DESIGN.md §3): tile-gathered matmuls with
static capacities cfg.sparsity.{ffn_tile_density, input_tile_density}.
"""
from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
from repro.core import activations as acts
from repro.models import common as cm
from repro.models import serving_protocol as sp
from jax import ad_checkpoint
from repro.sharding import rules

PyTree = Any

# ---------------------------------------------------------------------------
# attention sub-module (shared by every family with attention)


def attn_geometry(cfg: ModelConfig) -> cm.HeadGeometry:
    return cm.HeadGeometry(cfg.n_heads, cfg.n_kv_heads, cfg.resolved_head_dim)


def init_attn(rng, cfg: ModelConfig, dtype) -> PyTree:
    g = attn_geometry(cfg)
    d, hd = cfg.d_model, cfg.resolved_head_dim
    ks = jax.random.split(rng, 4)
    # init real heads, scatter into the padded per-group layout (zeros padded)
    wq = g.scatter_q(cm.dense_init(ks[0], (d, cfg.n_heads, hd), d, dtype), axis=1)
    if g.kvp == g.n_kv:
        wk = cm.dense_init(ks[1], (d, g.kvp, hd), d, dtype)
        wv = cm.dense_init(ks[2], (d, g.kvp, hd), d, dtype)
    else:  # MHA padding: zero K/V for padded kv heads
        wk = jnp.zeros((d, g.kvp, hd), dtype).at[:, : g.n_kv].set(
            cm.dense_init(ks[1], (d, g.n_kv, hd), d, dtype))
        wv = jnp.zeros((d, g.kvp, hd), dtype).at[:, : g.n_kv].set(
            cm.dense_init(ks[2], (d, g.n_kv, hd), d, dtype))
    wo = g.scatter_q(cm.dense_init(ks[3], (cfg.n_heads, hd, d), cfg.n_heads * hd, dtype),
                     axis=0)
    p = {"wq": wq, "wk": wk, "wv": wv, "wo": wo}
    if cfg.qkv_bias:
        p["bq"] = jnp.zeros((g.hp, hd), dtype)
        p["bk"] = jnp.zeros((g.kvp, hd), dtype)
        p["bv"] = jnp.zeros((g.kvp, hd), dtype)
    if cfg.qk_norm:
        p["q_norm"] = jnp.ones((hd,), dtype)
        p["k_norm"] = jnp.ones((hd,), dtype)
    return p


def _qkv(p, x, cfg: ModelConfig, positions, *, stats: cm.StatsCollector,
         input_density: float = 1.0):
    """x: (b, s, d) -> q (b,s,kvp,g,hd), k/v (b,s,kvp,hd). RoPE applied."""
    g = attn_geometry(cfg)
    stats.add_sparsity("qkv_in", x)
    b, s, d = x.shape
    x2 = x.reshape(b * s, d)
    wq = p["wq"].reshape(d, g.hp * g.head_dim)
    wk = p["wk"].reshape(d, g.kvp * g.head_dim)
    wv = p["wv"].reshape(d, g.kvp * g.head_dim)
    dens = input_density if cfg.sparsity.enabled else 1.0
    q = cm.maybe_sparse_matmul(x2, wq, cfg, dens).reshape(b, s, g.hp, g.head_dim)
    k = cm.maybe_sparse_matmul(x2, wk, cfg, dens).reshape(b, s, g.kvp, g.head_dim)
    v = cm.maybe_sparse_matmul(x2, wv, cfg, dens).reshape(b, s, g.kvp, g.head_dim)
    if cfg.qkv_bias:
        q = q + p["bq"]
        k = k + p["bk"]
        v = v + p["bv"]
    if cfg.qk_norm:
        q = cm.rms_norm_headdim(p["q_norm"], q)
        k = cm.rms_norm_headdim(p["k_norm"], k)
    if cfg.use_rope:
        q = cm.apply_rope(q, positions, cfg.rope_theta)
        k = cm.apply_rope(k, positions, cfg.rope_theta)
    return q, k, v  # q flat (b, s, hp, hd); k/v (b, s, kvp, hd)


def _attn_out(p, o, cfg: ModelConfig):
    """o: (b, s, hp, hd) -> (b, s, d), padded head slots masked."""
    g = attn_geometry(cfg)
    b, s = o.shape[:2]
    o = o * jnp.asarray(g.q_slot_mask(), o.dtype)[None, None, :, None]
    return jnp.einsum("bshd,hde->bse", o, p["wo"])


def apply_attn_full(
    p, x, cfg: ModelConfig, *, positions, causal=True, stats: cm.StatsCollector,
    return_kv=False, kv_override=None, q_offset: int = 0,
):
    """Full-sequence attention (train / prefill). Optionally returns K,V for
    the cache, or attends to externally supplied K,V (cross-attention).

    For GQA with kv < 16, K/V activations are replication-padded to 16 heads
    (each kv head repeated 16/kv times — exactly GQA, since every q head
    still sees a copy of its own kv head) so the attention einsums shard
    16-way over the `model` axis. Weights and the cache stay unpadded.
    """
    g = attn_geometry(cfg)
    b, s = x.shape[:2]
    q, k, v = _qkv(p, x, cfg, positions, stats=stats)
    # the copy stored into the prefill cache is SEQ-sharded over `model`
    # (matching the decode cache layout) so the stacked (L, b, S, kvp, hd)
    # buffer never materializes replicated on any chip
    kv_for_cache = (rules.constrain(k, "dp", "model", None, None),
                    rules.constrain(v, "dp", "model", None, None))
    if kv_override is not None:
        k, v = kv_override
        causal = False
    r = 1 if g.kvp % cm.TP == 0 else cm.TP // g.kvp
    if r > 1:
        k = jnp.repeat(k, r, axis=2)
        v = jnp.repeat(v, r, axis=2)
    kv_eff = g.kvp * r
    g_eff = g.hp // kv_eff
    qg = q.reshape(b, s, kv_eff, g_eff, g.head_dim)
    qg = rules.constrain(qg, "dp", None, "model", None, None)
    k = rules.constrain(k, "dp", None, "model", None)
    v = rules.constrain(v, "dp", None, "model", None)
    o = cm.flash_attention(qg, k, v, causal=causal, window=cfg.sliding_window,
                           q_offset=q_offset)
    out = _attn_out(p, o.reshape(b, s, g.hp, g.head_dim), cfg)
    if return_kv:
        return out, kv_for_cache
    return out


def apply_attn_decode(
    p, x, cfg: ModelConfig, k_cache, v_cache, pos, *, stats: cm.StatsCollector,
    cross: bool = False, layer=None,
):
    """One-token attention against a cache.

    x: (b, d); pos: (b,) write position. When ``layer`` is given, k_cache /
    v_cache are the FULL stacked (L, b, S, kvp, hd) buffers and only the
    single-token slice for this layer is written (the whole stack is carried
    through the layer scan so decode traffic is one cache read + an O(1)
    write — NOT a full rewrite). Otherwise they are per-layer (b, S, kvp, hd).
    cross=True skips the write (encoder K/V are static).
    Returns (out (b, d), k_cache, v_cache).
    """
    g = attn_geometry(cfg)
    q, k, v = _qkv(p, x[:, None, :], cfg, pos[:, None],
                   stats=stats, input_density=cfg.sparsity.input_tile_density)
    q = q.reshape(q.shape[0], 1, g.kvp, g.group, g.head_dim)
    if not cross:
        # uniform-position fast path: dynamic_update_slice is a single cheap
        # in-place update (positions are equal across the batch in the
        # dry-run serve step; the engine uses per-seq scatter instead).
        # cache is head-major: write (b, kvp, 1, hd) at position pos.
        kt = k.transpose(0, 2, 1, 3).astype(k_cache.dtype)  # (b, kvp, 1, hd)
        vt = v.transpose(0, 2, 1, 3).astype(v_cache.dtype)
        if layer is not None:
            k_cache = jax.lax.dynamic_update_slice(
                k_cache, kt[None], (layer, 0, 0, pos[0], 0))
            v_cache = jax.lax.dynamic_update_slice(
                v_cache, vt[None], (layer, 0, 0, pos[0], 0))
        else:
            k_cache = jax.lax.dynamic_update_slice(k_cache, kt, (0, 0, pos[0], 0))
            v_cache = jax.lax.dynamic_update_slice(v_cache, vt, (0, 0, pos[0], 0))
    if layer is not None:
        kl = jax.lax.dynamic_index_in_dim(k_cache, layer, 0, keepdims=False)
        vl = jax.lax.dynamic_index_in_dim(v_cache, layer, 0, keepdims=False)
    else:
        kl, vl = k_cache, v_cache
    o = cm.decode_attention(q[:, 0], kl, vl, pos, window=cfg.sliding_window)
    out = _attn_out(p, o.reshape(o.shape[0], 1, g.hp, g.head_dim), cfg)[:, 0]
    return out, k_cache, v_cache


# ---------------------------------------------------------------------------
# FFN sub-module (the paper's main stage — sparsity lives here)


def init_ffn(rng, cfg: ModelConfig, dtype, d_ff: Optional[int] = None) -> PyTree:
    d, F = cfg.d_model, d_ff or cfg.d_ff
    ks = jax.random.split(rng, 3)
    p = {"wu": cm.dense_init(ks[0], (d, F), d, dtype),
         "wd": cm.dense_init(ks[1], (F, d), F, dtype)}
    if cfg.ffn_kind == "glu":
        p["wg"] = cm.dense_init(ks[2], (d, F), d, dtype)
    return p


def apply_ffn(p, x, cfg: ModelConfig, *, stats: cm.StatsCollector,
              decode: bool = False, ffn_mask: Optional[jnp.ndarray] = None):
    """x: (tokens, d) -> (tokens, d). ffn_mask (d_ff,) emulates γ-window
    weight reuse (paper Fig. 7c): only previously-loaded rows participate."""
    act = acts.get(cfg.activation, shift=cfg.sparsity.shift)
    stats.add_sparsity("up_in", x)
    stats.add_raw("ffn_x", x)  # predictor-calibration capture (raw=True only)
    x = rules.constrain(x, "dp", None)
    dens_in = cfg.sparsity.input_tile_density if (cfg.sparsity.enabled and decode) else 1.0
    if cfg.ffn_kind == "glu":
        pre = cm.maybe_sparse_matmul(x, p["wg"], cfg, dens_in)
        stats.add_preact("ffn_pre", pre)
        h = act(pre) * cm.maybe_sparse_matmul(x, p["wu"], cfg, dens_in)
    else:
        pre = cm.maybe_sparse_matmul(x, p["wu"], cfg, dens_in)
        stats.add_preact("ffn_pre", pre)
        h = act(pre)
    if ffn_mask is not None:
        h = h * ffn_mask.astype(h.dtype)
    stats.add_sparsity("down_in", h)
    if stats.active:  # unit-level activity for aggregated-sparsity tracking
        stats.add("down_act", jnp.any(h != 0, axis=0))
    h = rules.constrain(h, "dp", "model")
    dens_ffn = cfg.sparsity.ffn_tile_density if (cfg.sparsity.enabled and decode) else 1.0
    return rules.constrain(
        cm.maybe_sparse_matmul(h, p["wd"], cfg, dens_ffn), "dp", None)


def post_norm(x, cfg: ModelConfig):
    """Relufication stage 2: ReLU after the normalization layer."""
    return jax.nn.relu(x) if cfg.post_norm_relu else x


# ---------------------------------------------------------------------------
# dense decoder blocks


def init_block(rng, cfg: ModelConfig, dtype) -> PyTree:
    ks = jax.random.split(rng, 2)
    return {
        "ln1": cm.init_norm(cfg, cfg.d_model, dtype),
        "attn": init_attn(ks[0], cfg, dtype),
        "ln2": cm.init_norm(cfg, cfg.d_model, dtype),
        "ffn": init_ffn(ks[1], cfg, dtype),
    }


def apply_block(p, x, cfg: ModelConfig, *, positions, stats, return_kv=False,
                causal=True):
    with jax.named_scope("attention"):
        h = post_norm(cm.apply_norm(p["ln1"], x, cfg), cfg)
        if return_kv:
            a, kv = apply_attn_full(p["attn"], h, cfg, positions=positions,
                                    stats=stats, return_kv=True,
                                    causal=causal)
        else:
            a = apply_attn_full(p["attn"], h, cfg, positions=positions,
                                stats=stats, causal=causal)
        a = ad_checkpoint.checkpoint_name(a, "attn_out")  # TP all-reduce
        x = x + a
    with jax.named_scope("ffn"):
        h = post_norm(cm.apply_norm(p["ln2"], x, cfg), cfg)
        b, s, d = h.shape
        f = apply_ffn(p["ffn"], h.reshape(b * s, d), cfg,
                      stats=stats).reshape(b, s, d)
        f = ad_checkpoint.checkpoint_name(f, "ffn_out")  # TP all-reduce
        x = x + f
    if cfg.sp_residuals:
        x = rules.constrain(x, "dp", None, "model")
    return (x, kv) if return_kv else x


def apply_block_decode(p, x, cfg: ModelConfig, k_cache, v_cache, pos, *, stats,
                       layer=None, ffn_mask=None):
    h = post_norm(cm.apply_norm(p["ln1"], x[:, None], cfg)[:, 0], cfg)
    a, k_cache, v_cache = apply_attn_decode(
        p["attn"], h, cfg, k_cache, v_cache, pos, stats=stats, layer=layer)
    x = x + a
    h = post_norm(cm.apply_norm(p["ln2"], x[:, None], cfg)[:, 0], cfg)
    f = apply_ffn(p["ffn"], h, cfg, stats=stats, decode=True, ffn_mask=ffn_mask)
    x = x + f
    return x, k_cache, v_cache


# ---------------------------------------------------------------------------
# whole model


def init_params(rng, cfg: ModelConfig) -> PyTree:
    dtype = jnp.dtype(cfg.param_dtype)
    vp = cm.padded_vocab(cfg.vocab_size)
    ks = jax.random.split(rng, 4)
    layer_keys = jax.random.split(ks[0], cfg.n_layers)
    layers = jax.vmap(lambda k: init_block(k, cfg, dtype))(layer_keys)
    p = {
        "embed": cm.embed_init(ks[1], (vp, cfg.d_model), dtype),
        "layers": layers,
        "final_norm": cm.init_norm(cfg, cfg.d_model, dtype),
    }
    if not cfg.tie_embeddings:
        p["unembed"] = cm.embed_init(ks[2], (vp, cfg.d_model), dtype)
    if not cfg.use_rope:
        p["pos_embed"] = cm.embed_init(ks[3], (cfg.max_seq_len, cfg.d_model), dtype)
    return p


def embed_tokens(params, tokens, cfg: ModelConfig, positions):
    x = jnp.take(params["embed"], tokens, axis=0).astype(jnp.dtype(cfg.compute_dtype))
    if not cfg.use_rope:
        pe = jnp.take(params["pos_embed"], positions, axis=0)
        x = x + pe.astype(x.dtype)
    return x


def logits_from(params, x, cfg: ModelConfig):
    with jax.named_scope("head"):
        u = params.get("unembed", params["embed"])
        out = jnp.einsum("...d,vd->...v", x, u.astype(x.dtype))
        return out + cm.vocab_logit_mask(cfg.vocab_size,
                                         u.shape[0]).astype(out.dtype)


def forward(params, tokens, cfg: ModelConfig, *, stats: Optional[cm.StatsCollector] = None,
            extra_embeds: Optional[jnp.ndarray] = None, return_kv: bool = False,
            remat_block=None, last_index=None):
    """Full-sequence forward. tokens: (b, s) -> logits (b, s_total, vocab_p).

    extra_embeds (b, n, d): modality-frontend stubs (vision patches / audio
    frames) prepended to the token embeddings (internvl2).

    last_index: optional TRACED scalar — with return_kv, take the prefill
    logits from this position instead of s-1 (tokens beyond it are padding;
    causality keeps the earlier positions exact).
    """
    stats = stats or cm.StatsCollector(False)
    params = cm.cast_params(params, cfg)
    b, s = tokens.shape
    positions = jnp.broadcast_to(jnp.arange(s), (b, s))
    x = embed_tokens(params, tokens, cfg, positions)
    if extra_embeds is not None:
        x = jnp.concatenate([extra_embeds.astype(x.dtype), x], axis=1)
        s = x.shape[1]
        positions = jnp.broadcast_to(jnp.arange(s), (b, s))

    x = rules.constrain(x, "dp", None, None)
    block = remat_block or apply_block

    if stats.active:  # unrolled so per-layer stats stay distinguishable
        kvs = []
        layers = params["layers"]
        for i in range(cfg.n_layers):
            pl_i = jax.tree.map(lambda a: a[i], layers)
            sub = cm.StatsCollector(True, raw=stats.raw)
            if return_kv:
                x, kv = block(pl_i, x, cfg, positions=positions, stats=sub,
                              return_kv=True)
                kvs.append(kv)
            else:
                x = block(pl_i, x, cfg, positions=positions, stats=sub)
            for k_, v_ in sub.stats.items():
                stats.stats[f"layer{i}/{k_}"] = v_
        kv_stack = (jax.tree.map(lambda *a: jnp.stack(a), *kvs) if kvs else None)
    else:
        def body(x, pl_i):
            if return_kv:
                x, kv = block(pl_i, x, cfg, positions=positions, stats=stats,
                              return_kv=True)
                return x, kv
            return block(pl_i, x, cfg, positions=positions, stats=stats), None
        x, kv_stack = jax.lax.scan(body, x, params["layers"])

    x = cm.apply_norm(params["final_norm"], x, cfg)
    if return_kv:
        # prefill: only the last position's logits are needed -> avoid the
        # (b, s, vocab_p) buffer entirely
        xl = (x[:, -1:] if last_index is None
              else jax.lax.dynamic_slice_in_dim(x, last_index, 1, axis=1))
        logits = logits_from(params, xl, cfg)
        return logits, kv_stack
    return logits_from(params, x, cfg)


# ---------------------------------------------------------------------------
# serving entry points


def init_cache(cfg: ModelConfig, batch: int, max_len: int, dtype=None) -> PyTree:
    g = attn_geometry(cfg)
    dtype = dtype or jnp.dtype(cfg.compute_dtype)
    # head-major layout: decode einsums read it without transposing
    shape = (cfg.n_layers, batch, g.kvp, max_len, g.head_dim)
    return {"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype)}


def finalize_prefill_cache(k, v, max_len: int):
    """(L, b, s, kvp, hd) scan output -> head-major padded cache dict."""
    k = k.transpose(0, 1, 3, 2, 4)  # -> head-major (L, b, kvp, s, hd)
    v = v.transpose(0, 1, 3, 2, 4)
    pad = max_len - k.shape[3]
    if pad > 0:
        zeros = jnp.zeros(k.shape[:3] + (pad,) + k.shape[4:], k.dtype)
        k = jnp.concatenate([k, zeros], axis=3)
        v = jnp.concatenate([v, zeros], axis=3)
    return {"k": k, "v": v}


def prefill(params, tokens, cfg: ModelConfig, max_len: int,
            stats: Optional[cm.StatsCollector] = None):
    """Run the full prompt, return (last-token logits, cache at max_len)."""
    logits, kv = forward(params, tokens, cfg, stats=stats, return_kv=True)
    # logits are last-position only (b, 1, V)
    return logits[:, -1], finalize_prefill_cache(*kv, max_len)


def decode_step(params, cache, token, pos, cfg: ModelConfig,
                stats: Optional[cm.StatsCollector] = None,
                ffn_masks: Optional[jnp.ndarray] = None):
    """One decode step. token: (b,) int32; pos: (b,) write position.

    ffn_masks (L, d_ff): γ-window weight-reuse masks (paper Fig. 7c).
    Returns (logits (b, vocab_p), new cache). The cache S axis may be sharded
    (long-context flash-decode, DESIGN.md §3).
    """
    stats = stats or cm.StatsCollector(False)
    params = cm.cast_params(params, cfg)
    b = token.shape[0]
    x = embed_tokens(params, token[:, None], cfg, pos[:, None])[:, 0]

    if stats.active:
        kc, vc = cache["k"], cache["v"]
        for i in range(cfg.n_layers):
            pl_i = jax.tree.map(lambda a: a[i], params["layers"])
            sub = cm.StatsCollector(True)
            x, kc, vc = apply_block_decode(
                pl_i, x, cfg, kc, vc, pos, stats=sub, layer=i,
                ffn_mask=None if ffn_masks is None else ffn_masks[i])
            for k_, v_ in sub.stats.items():
                stats.stats[f"layer{i}/{k_}"] = v_
        new_cache = {"k": kc, "v": vc}
    else:
        # the FULL stacked cache rides in the scan carry: per step each layer
        # reads its slice for attention and writes one token in place (no
        # per-layer full-slice rewrites through scan ys).
        def body(carry, xs):
            x, kc, vc = carry
            if ffn_masks is None:
                pl_i, li = xs
                fm = None
            else:
                pl_i, li, fm = xs
            x, kc, vc = apply_block_decode(pl_i, x, cfg, kc, vc, pos,
                                           stats=stats, layer=li, ffn_mask=fm)
            return (x, kc, vc), None
        xs = ((params["layers"], jnp.arange(cfg.n_layers)) if ffn_masks is None
              else (params["layers"], jnp.arange(cfg.n_layers), ffn_masks))
        (x, kc, vc), _ = jax.lax.scan(
            body, (x, cache["k"], cache["v"]), xs)
        new_cache = {"k": kc, "v": vc}

    x = cm.apply_norm(params["final_norm"], x[:, None], cfg)[:, 0]
    logits = logits_from(params, x, cfg)
    return logits, new_cache


# ---------------------------------------------------------------------------
# continuous-batching serving: paged cache + per-request γ-window masks
#
# Unlike decode_step above (uniform positions, contiguous per-batch cache),
# these entry points serve a *slot* batch whose requests were admitted at
# different times: every slot has its own write position, its own block-table
# row into the shared page pool, and its own γ-window FFN mask + refresh
# phase. The whole stack is written for a W-token WINDOW per slot — W = γ+1
# is the speculative-verification target forward (all window tokens in ONE
# pass, causal within the window), W = 1 is the plain decode step. Everything
# is computed in-graph — one trace, no host round-trips.


def _ffn_tile(cfg: ModelConfig) -> int:
    return cm.ffn_gather_tile(cfg)


def apply_attn_window_paged(p, x, cfg: ModelConfig, k_pages, v_pages, table,
                            pos, valid, *, layer, block_size: int,
                            stats: cm.StatsCollector,
                            fast_kernels: bool = False):
    """W-token windowed attention against the paged pool. x: (b, W, d);
    pos: (b, W) per-slot write positions (NOT uniform); valid: (b, W) real
    window tokens — K/V of invalid ones is routed to the scratch block;
    table: (b, nb) block ids. Causal within the window: token i attends to
    cache positions <= pos[:, i]. Returns (out (b, W, d), k_pages, v_pages).

    ``fast_kernels`` reads the stacked pool THROUGH the block table inside
    a Pallas kernel (kernels/paged_attention.py) instead of slicing the
    layer and materializing the ``paged_gather`` copy, and writes the
    window's K/V with the aliased Pallas writer — same math (streams match
    at f32), half the cache traffic, and no pool-sized copy.
    """
    g = attn_geometry(cfg)
    b, W, _ = x.shape
    q, k, v = _qkv(p, x, cfg, pos, stats=stats,
                   input_density=cfg.sparsity.input_tile_density)
    q = q.reshape(b, W, g.kvp, g.group, g.head_dim)
    with jax.named_scope("kv_write"):
        k_pages = cm.paged_write_window(k_pages, layer, table, pos, k,
                                        block_size, valid,
                                        fast_kernels=fast_kernels)
        v_pages = cm.paged_write_window(v_pages, layer, table, pos, v,
                                        block_size, valid,
                                        fast_kernels=fast_kernels)
    if fast_kernels:
        from repro.kernels import paged_attention as kpa
        o = kpa.paged_window_attention(q, k_pages, v_pages, table, pos,
                                       layer, window=cfg.sliding_window)
    else:
        kl = jax.lax.dynamic_index_in_dim(k_pages, layer, 0, keepdims=False)
        vl = jax.lax.dynamic_index_in_dim(v_pages, layer, 0, keepdims=False)
        kg = cm.paged_gather(kl, table)
        vg = cm.paged_gather(vl, table)
        o = cm.window_attention(q, kg, vg, pos, window=cfg.sliding_window)
    out = _attn_out(p, o.reshape(b, W, g.hp, g.head_dim), cfg)
    return out, k_pages, v_pages


def apply_ffn_window(p, x, cfg: ModelConfig, *, mask, refresh, valid,
                     fast_kernels: bool = False):
    """Decode FFN over a W-token window with per-request γ-window weight
    reuse, batched over slots. x: (b, W, d); mask: (b, F) bool — the rows
    loaded in each request's current window; refresh: (b,) bool — slots
    starting a new window this step (they run dense and record fresh
    activity); valid: (b, W) bool — real window tokens (idle slots and
    window padding are excluded from activity/scores).

    Returns (out (b, W, d),
             act (b, F) union activity over the window's valid tokens,
             scores (b, F//tile) window-union tile activity,
             density (b,) fraction of down-proj rows READ (refresh ⇒ 1.0)
                 — the Fig. 7c γ-reuse weight-I/O metric,
             union_density (b,) fraction of rows in the window's activity
                 union = 1 − s_agg(W) — the Sec. 5.2 sparse-verification
                 I/O metric).

    ``fast_kernels`` makes the union I/O saving PHYSICAL: the
    down-projection runs as a per-row tile gather (sparse_matmul_tokens)
    over each slot's window-union tile list, so only union-active wd tiles
    are read — exactly the density the union_density metric reports. The up
    projection stays dense (the union is only known after it runs)."""
    from repro.kernels.fused_ffn import window_tile_activity

    act_fn = acts.get(cfg.activation, shift=cfg.sparsity.shift)
    b, W, d = x.shape
    x2 = x.reshape(b * W, d)
    dens_in = (cfg.sparsity.input_tile_density if cfg.sparsity.enabled
               else 1.0)
    if cfg.ffn_kind == "glu":
        pre = cm.maybe_sparse_matmul(x2, p["wg"], cfg, dens_in)
        h = act_fn(pre) * cm.maybe_sparse_matmul(x2, p["wu"], cfg, dens_in)
    else:
        h = act_fn(cm.maybe_sparse_matmul(x2, p["wu"], cfg, dens_in))
    h = h.reshape(b, W, h.shape[-1])
    # TP serving: window activations / union masks live on shard-local d_ff
    # slices (no-op single-device — constrain is identity without a mesh)
    h = rules.constrain(h, "dp", None, "model")
    eff = mask | refresh[:, None]  # refresh ⇒ all rows participate
    h = h * eff[:, None, :].astype(h.dtype)
    hv = h * valid[:, :, None].astype(h.dtype)
    act = jnp.any(hv != 0, axis=1)  # (b, F) union over the window
    scores = window_tile_activity(hv, _ffn_tile(cfg))
    density = jnp.mean(eff.astype(jnp.float32), axis=-1)
    union_density = jnp.mean(act.astype(jnp.float32), axis=-1)
    dens_ffn = (cfg.sparsity.ffn_tile_density if cfg.sparsity.enabled
                else 1.0)
    if fast_kernels and dens_in >= 1.0 and dens_ffn >= 1.0:
        from repro.kernels import sparse_matmul as ksm
        from repro.predictor import predictors as preds
        tile = _ffn_tile(cfg)
        n_tiles = h.shape[-1] // tile
        # per-slot window-union tile list at full capacity: valid rows'
        # support is inside their slot's union, so gathering only union
        # tiles is exact for every row the caller reads (invalid window
        # rows may differ — their outputs are discarded by construction)
        idx, nvalid = preds.pack_tile_indices(scores > 0, n_tiles)
        out = ksm.sparse_matmul_tokens(
            h.reshape(b * W, -1).astype(p["wd"].dtype), p["wd"],
            jnp.repeat(idx, W, axis=0), jnp.repeat(nvalid, W),
            tile=tile).astype(x.dtype)
    else:
        out = cm.maybe_sparse_matmul(h.reshape(b * W, -1), p["wd"], cfg,
                                     dens_ffn)
    return out.reshape(b, W, d), act, scores, density, union_density


def apply_block_window_paged(p, x, cfg: ModelConfig, k_pages, v_pages, table,
                             pos, valid, *, layer, block_size: int, mask,
                             refresh, fast_kernels: bool = False):
    stats = cm.StatsCollector(False)
    with jax.named_scope("attention"):
        h = post_norm(cm.apply_norm(p["ln1"], x, cfg), cfg)
        a, k_pages, v_pages = apply_attn_window_paged(
            p["attn"], h, cfg, k_pages, v_pages, table, pos, valid,
            layer=layer, block_size=block_size, stats=stats,
            fast_kernels=fast_kernels)
        x = x + a
    with jax.named_scope("ffn"):
        h = post_norm(cm.apply_norm(p["ln2"], x, cfg), cfg)
        f, act, scores, density, udens = apply_ffn_window(
            p["ffn"], h, cfg, mask=mask, refresh=refresh, valid=valid,
            fast_kernels=fast_kernels)
        x = x + f
    return x, k_pages, v_pages, act, scores, density, udens


def verify_window_paged(params, pages, table, tokens, pos0, wlen,
                        cfg: ModelConfig, ffn_masks, refresh, *,
                        block_size: int, fast_kernels: bool = False):
    """Run a W-token window per slot in ONE forward over the shared page
    pool — the speculative-verification target step (paper Sec. 5.2): every
    window token's K/V is written at its own position, attention is causal
    within the window, and the FFN activity comes back as the window's
    aggregated (union) mask. W == 1 is exactly the plain continuous-batching
    decode step (see ``decode_step_paged``).

    tokens: (b, W) = [current token, draft proposals...]; pos0: (b,) write
    position of tokens[:, 0]; wlen: (b,) valid window length per slot —
    tokens at index >= wlen (and every token of an idle slot, wlen == 0)
    write to the scratch block and are excluded from activity, so no
    speculative write can land outside a request's blocks; table: (b, nb);
    ffn_masks: (L, b, F) bool γ-window masks; refresh: (b,).

    Returns (logits (b, W, vocab_p), pages, new_masks (L, b, F), aux) with
    aux = (act (L, b, F) window-union FFN activity, scores (L, b, F//tile)
    window-union tile activity, density (L, b) fraction of rows read,
    union_density (L, b) = 1 − s_agg of each slot's window).

    Structure (embed → layer scan → mask refresh → head) lives in the
    family-agnostic ``serving_protocol.window_step_core``; this wrapper
    only supplies the dense block — the delegated trace is op-for-op the
    historical lowering."""
    def layer_fn(pl_i, li, x, kp, vp, fm, pos, valid):
        x, kp, vp, act, scores, density, udens = apply_block_window_paged(
            pl_i, x, cfg, kp, vp, table, pos, valid, layer=li,
            block_size=block_size, mask=fm, refresh=refresh,
            fast_kernels=fast_kernels)
        return x, kp, vp, (act, scores, density, udens)

    return sp.window_step_core(params, pages, tokens, pos0, wlen, cfg,
                               ffn_masks, refresh, layer_fn=layer_fn,
                               embed_fn=embed_tokens, logits_fn=logits_from)


def prefill_chunk_paged(params, pages, table, tokens, pos0, clen,
                        cfg: ModelConfig, ffn_masks, refresh, *,
                        block_size: int, fast_kernels: bool = False):
    """One fixed-shape CHUNK of paged prefill, batched over slots — the
    admission path that replaces stop-the-world whole-prompt prefill.

    A prefill chunk IS a W-token window step, so this delegates to
    ``verify_window_paged``: every chunk token's K/V is scattered at its own
    position through the block table (``paged_write_window``), attention is
    causal within the chunk and over everything already in the cache
    (earlier chunks AND prefix-cache blocks written by other requests), and
    tokens at index >= clen are scratch-routed. The scheduler interleaves
    one chunk per engine step with decode, so admission costs ONE compiled
    shape (n_slots × chunk) with bounded per-step latency — instead of one
    whole-prompt executable per prompt-block count, each stalling every
    active decode for its full duration.

    tokens: (b, C) the next C prompt tokens per slot (zero-padded past
    clen); pos0: (b,) each slot's prefill resume position — block-aligned
    for a prefix-cache hit's cold suffix; clen: (b,) valid chunk lengths
    (0 = slot not prefilling this step).

    Returns (logits (b, C, vocab_p), pages, new_masks, aux): on a request's
    final chunk, logits[i, clen_i - 1] seed its first generated token; aux's
    union FFN activity / tile scores are the free per-chunk harvest that
    warms the request's first γ-window mask and predictor telemetry
    (new_masks picks it up wherever ``refresh`` is set)."""
    return verify_window_paged(params, pages, table, tokens, pos0, clen,
                               cfg, ffn_masks, refresh,
                               block_size=block_size,
                               fast_kernels=fast_kernels)


def _ffn_decode_predicted(pf, h, cfg: ModelConfig, pred_l, *, kind: str,
                          tile: int, k_tiles: int, mask, refresh,
                          measure: bool = True, shards: int = 1,
                          fast_kernels: bool = False, ffn_stack=None,
                          layer=None):
    """Predictor-gathered decode FFN (predictor serving mode): the
    activity predictor (repro.predictor) names each token's active tiles
    BEFORE any FFN weight is read, and both the up- and down-projections
    run as tile-gathered matmuls (kernels/sparse_matmul.py) over exactly
    those tiles — the paper's "up to 3x" headroom applied to the full FFN
    weight I/O, not just the down-projection.

    h: (B, d) post-norm FFN input; pred_l: this layer's predictor-param
    slice; mask (B, F) / refresh (B,): the γ-window machinery — between
    refreshes the window's rows are composed INTO the predicted set
    (cheap recall insurance: recently-active rows stay computable even if
    the probe misses them).

    A recall miss is a correctness event, so with measure=True (the
    measurement-repo default) it is counted in-graph: a dense gate
    pre-activation — telemetry only, its product never feeds the residual
    stream — re-reads the gate weight each step. measure=False drops that
    probe (n_active/n_miss come back 0), making the gathered reads the
    ONLY FFN weight traffic — the production-serving configuration.

    ``ffn_stack`` / ``layer``: with fast kernels, the scan's whole FFN
    weight stacks and this layer's index, which the fused kernel reads
    its tiles from in place (``pf`` is this layer's slice of them).

    ``shards`` (the engine passes its mesh's TP degree; 1 = today's
    single-device lowering, bit-frozen) makes the packed tile lists
    model-axis-local: each TP shard packs its own capacity from its local
    d_ff slice (predictors.pack_tile_indices n_groups), the probe /
    union-mask composition runs on "model"-sharded (B, F) tensors, and
    the per-token density/recall telemetry is reduced across shards once
    per step by the returned sums — no host round-trips.

    Returns (f (B, d), act (B, F), scores (B, F // _ffn_tile),
             density (B,) fraction of weight tiles READ (up AND down),
             n_active (B,), n_miss (B,), ffn_tiles) — ``ffn_tiles`` is
             ``no_ffn_tiles`` off the fused kernel, else its
             ``fused_decode.tile_counts``."""
    from repro.kernels import sparse_matmul as ksm
    from repro.kernels.fused_ffn import tile_activity
    from repro.predictor import predictors as preds

    act_fn = acts.get(cfg.activation, shift=cfg.sparsity.shift)
    n_tiles = cfg.d_ff // tile
    unit_pred = preds.predict_units(kind, pred_l, h)  # (B, F)
    unit_pred = rules.constrain(unit_pred, "dp", "model")
    eff_units = unit_pred | (mask & ~refresh[:, None])
    tile_mask = preds.units_to_tiles(eff_units, tile)
    idx, nvalid = preds.pack_tile_indices(tile_mask, k_tiles,
                                          n_groups=shards)
    cov_units = preds.tiles_to_units(
        preds.covered_tiles(idx, nvalid, n_tiles), tile)  # (B, F)

    gate_w = pf["wg"] if cfg.ffn_kind == "glu" else pf["wu"]
    ffn_tiles = sp.no_ffn_tiles(h.shape[0])
    if fast_kernels:
        # fused gather-up -> act -> scatter-down: one DMA per tile of the
        # batch's union, each token's tiles summed in the same order as the
        # unfused pair below (bit-equal — tests/test_fused_decode.py). The
        # kernel multiplies each token's rows by its membership of the
        # tile (cov_units), so tiles other tokens named stay exact zeros.
        from repro.kernels import fused_decode as kfd
        glu = cfg.ffn_kind == "glu"
        f32, hh = kfd.fused_sparse_ffn(
            h, ffn_stack["wg" if glu else "wu"], ffn_stack["wd"], idx,
            nvalid, w_up=ffn_stack["wu"] if glu else None, layer=layer,
            activation=cfg.activation, shift=cfg.sparsity.shift, tile=tile)
        ffn_tiles = kfd.tile_counts(idx, nvalid, n_tiles)
        f = f32.astype(h.dtype)
    else:
        pre = ksm.sparse_up_matmul(h, gate_w, idx, nvalid, tile=tile)
        # mask to the covered tiles so skipped tiles are EXACTLY zero even
        # for activations with f(0) != 0 (e.g. negative shifted_relu shift)
        hh = act_fn(pre) * cov_units.astype(pre.dtype)
        if cfg.ffn_kind == "glu":
            hh = hh * ksm.sparse_up_matmul(h, pf["wu"], idx, nvalid,
                                           tile=tile)
        f = ksm.sparse_matmul_tokens(hh.astype(pf["wd"].dtype), pf["wd"],
                                     idx, nvalid, tile=tile).astype(h.dtype)
    act = hh != 0
    scores = tile_activity(hh, _ffn_tile(cfg))
    density = nvalid.astype(jnp.float32) / n_tiles

    if measure:
        thr = acts.firing_threshold(cfg.activation, cfg.sparsity.shift)
        true_act = (h @ gate_w).astype(jnp.float32) > thr  # telemetry only
        n_active = jnp.sum(true_act.astype(jnp.int32), axis=-1)
        n_miss = jnp.sum((true_act & ~cov_units).astype(jnp.int32), axis=-1)
    else:
        n_active = jnp.zeros(h.shape[0], jnp.int32)
        n_miss = jnp.zeros(h.shape[0], jnp.int32)
    return f, act, scores, density, n_active, n_miss, ffn_tiles


def apply_block_decode_paged(p, x, cfg: ModelConfig, k_pages, v_pages, table,
                             pos, *, layer, block_size: int, mask, refresh,
                             pred=None, pred_kind: Optional[str] = None,
                             pred_tile: int = 128, k_tiles: int = 0,
                             pred_measure: bool = True, pred_shards: int = 1,
                             fast_kernels: bool = False, ffn_stack=None):
    """Single-token specialization of ``apply_block_window_paged``.

    Mathematically the W = 1 case, but kept as its own lowering: the decode
    step is the latency-critical path (it should carry no window machinery),
    and its bf16 rounding placement is FROZEN — re-deriving it from the
    window code changes where XLA rounds, which changes greedy outputs of
    bf16 models across engines (exactness tests pin the current numerics).

    ``pred`` (a per-layer predictor-param slice; None = off, identical
    trace to before) switches the FFN to the predictor-gathered path
    (``_ffn_decode_predicted``), which appends (n_active, n_miss) recall
    telemetry to the return tuple. Either way the tuple ends with
    ``ffn_tiles``: (tiles each slot named (B,), tiles fetched ()) through
    the fused kernel, zeros where the layer did not run it.

    ``ffn_stack``: with ``fast_kernels``, the layer scan's whole FFN weight
    stacks (``_ffn_stack``); the fused kernel reads layer ``layer``'s tiles
    from them in place, where a kernel operand sliced from ``p`` would
    make XLA copy the layer's weights first.
    """
    stats = cm.StatsCollector(False)
    g = attn_geometry(cfg)
    with jax.named_scope("attention"):
        h = post_norm(cm.apply_norm(p["ln1"], x[:, None], cfg)[:, 0], cfg)
        q, k, v = _qkv(p["attn"], h[:, None, :], cfg, pos[:, None],
                       stats=stats,
                       input_density=cfg.sparsity.input_tile_density)
        q = q.reshape(q.shape[0], g.kvp, g.group, g.head_dim)
    with jax.named_scope("kv_write"):
        k_pages = cm.paged_write_token(k_pages, layer, table, pos, k[:, 0],
                                       block_size, fast_kernels=fast_kernels)
        v_pages = cm.paged_write_token(v_pages, layer, table, pos, v[:, 0],
                                       block_size, fast_kernels=fast_kernels)
    with jax.named_scope("attention"):
        if fast_kernels:
            from repro.kernels import paged_attention as kpa
            o = kpa.paged_decode_attention(q, k_pages, v_pages, table, pos,
                                           layer, window=cfg.sliding_window)
        else:
            kl = jax.lax.dynamic_index_in_dim(k_pages, layer, 0,
                                              keepdims=False)
            vl = jax.lax.dynamic_index_in_dim(v_pages, layer, 0,
                                              keepdims=False)
            kg = cm.paged_gather(kl, table)
            vg = cm.paged_gather(vl, table)
            o = cm.decode_attention(q, kg, vg, pos,
                                    window=cfg.sliding_window)
        a = _attn_out(p["attn"], o.reshape(o.shape[0], 1, g.hp, g.head_dim),
                      cfg)[:, 0]
        x = x + a
    from repro.kernels.fused_ffn import tile_activity
    with jax.named_scope("ffn"):
        h = post_norm(cm.apply_norm(p["ln2"], x[:, None], cfg)[:, 0], cfg)
        if pred is not None:
            (f, act, scores, density, n_active, n_miss,
             ffn_tiles) = _ffn_decode_predicted(
                p["ffn"], h, cfg, pred, kind=pred_kind, tile=pred_tile,
                k_tiles=k_tiles, mask=mask, refresh=refresh,
                measure=pred_measure, shards=pred_shards,
                fast_kernels=fast_kernels, ffn_stack=ffn_stack, layer=layer)
            x = x + f
            return (x, k_pages, v_pages, act, scores, density, n_active,
                    n_miss, ffn_tiles)
        act_fn = acts.get(cfg.activation, shift=cfg.sparsity.shift)
        dens_in = (cfg.sparsity.input_tile_density if cfg.sparsity.enabled
                   else 1.0)
        dens_ffn = (cfg.sparsity.ffn_tile_density if cfg.sparsity.enabled
                    else 1.0)
        pf = p["ffn"]
        eff = mask | refresh[:, None]  # refresh ⇒ all rows participate
        ffn_tiles = sp.no_ffn_tiles(h.shape[0])
        if fast_kernels and dens_in >= 1.0 and dens_ffn >= 1.0:
            # AR fast path: the γ-window eff mask IS a per-token active
            # set, so the whole FFN runs through the fused kernel over eff's
            # tile list at full capacity — up- AND down-projection reads of
            # tiles no token's mask touches are physically skipped, and
            # each tile the batch names is read once; masked-off units
            # inside gathered tiles are zeroed in-kernel (unit_mask),
            # matching hh * eff.
            from repro.kernels import fused_decode as kfd
            from repro.predictor import predictors as preds
            tile = _ffn_tile(cfg)
            n_tiles = cfg.d_ff // tile
            idx, nvalid = preds.pack_tile_indices(
                preds.units_to_tiles(eff, tile), n_tiles)
            glu = cfg.ffn_kind == "glu"
            f32, hh = kfd.fused_sparse_ffn(
                h, ffn_stack["wg" if glu else "wu"], ffn_stack["wd"], idx,
                nvalid, w_up=ffn_stack["wu"] if glu else None, layer=layer,
                unit_mask=eff, activation=cfg.activation,
                shift=cfg.sparsity.shift, tile=tile)
            ffn_tiles = kfd.tile_counts(idx, nvalid, n_tiles)
            f = f32.astype(h.dtype)
        else:
            if cfg.ffn_kind == "glu":
                pre = cm.maybe_sparse_matmul(h, pf["wg"], cfg, dens_in)
                hh = act_fn(pre) * cm.maybe_sparse_matmul(h, pf["wu"], cfg,
                                                          dens_in)
            else:
                hh = act_fn(cm.maybe_sparse_matmul(h, pf["wu"], cfg,
                                                   dens_in))
            # TP serving (rules.use_mesh installed): keep the hidden
            # activation and the γ-mask composition sharded on each shard's
            # d_ff slice; no-op (and bit-frozen lowering) single-device
            hh = rules.constrain(hh, "dp", "model")
            hh = hh * eff.astype(hh.dtype)
            f = cm.maybe_sparse_matmul(hh, pf["wd"], cfg, dens_ffn)
        act = hh != 0
        scores = tile_activity(hh, _ffn_tile(cfg))
        density = jnp.mean(eff.astype(jnp.float32), axis=-1)
        x = x + f
        return x, k_pages, v_pages, act, scores, density, ffn_tiles


def _ffn_stack(params, cfg: ModelConfig, fast_kernels: bool):
    """The FFN weights of every layer, stacked and in the compute dtype,
    as the fused decode kernel reads them in place (None without fast
    kernels, whose FFN reads the scan's per-layer slice)."""
    if not fast_kernels:
        return None
    return cm.cast_params(params["layers"]["ffn"], cfg)


def decode_step_paged(params, pages, table, token, pos, cfg: ModelConfig,
                      ffn_masks, refresh, *, block_size: int,
                      fast_kernels: bool = False):
    """One continuous-batching decode step over the shared page pool — the
    W = 1 case of ``verify_window_paged``, specialized (see
    ``apply_block_decode_paged`` for why it is not a wrapper).

    token/pos/refresh: (b,) per slot; table: (b, nb); ffn_masks: (L, b, F)
    bool γ-window masks. Idle slots point at the scratch block and are
    simply ignored by the caller. Returns (logits (b, vocab_p), pages,
    new_masks (L, b, F), aux) where aux = (act (L, b, F), scores
    (L, b, F//tile), density (L, b), ffn_tiles (tiles named (L, b), tiles
    fetched (L,)) through the fused kernel, zeros without it).

    Structure lives in ``serving_protocol.decode_step_core``; this wrapper
    supplies the dense decode block (same jaxpr as the historical inline
    loop)."""
    ffn_stack = _ffn_stack(params, cfg, fast_kernels)

    def layer_fn(pl_i, li, x, kp, vp, fm):
        x, kp, vp, act, scores, density, ffn_tiles = \
            apply_block_decode_paged(
                pl_i, x, cfg, kp, vp, table, pos, layer=li,
                block_size=block_size, mask=fm, refresh=refresh,
                fast_kernels=fast_kernels, ffn_stack=ffn_stack)
        return x, kp, vp, (act, scores, density, ffn_tiles)

    return sp.decode_step_core(params, pages, token, pos, cfg, ffn_masks,
                               refresh, layer_fn=layer_fn,
                               embed_fn=embed_tokens, logits_fn=logits_from)


def decode_step_paged_predicted(params, pages, table, token, pos, cfg: ModelConfig,
                                ffn_masks, refresh, pred_params, *,
                                kind: str, tile: int, k_tiles: int,
                                block_size: int, measure: bool = True,
                                shards: int = 1, fast_kernels: bool = False):
    """Predictor-mode continuous-batching decode step: like
    ``decode_step_paged`` but every layer's FFN runs tile-gathered over the
    activity predictor's per-token mask (up- AND down-projection reads are
    skipped — see ``_ffn_decode_predicted``). pred_params is the stacked
    (leading layer axis) predictor pytree; kind / tile / k_tiles are static
    so the step compiles ONCE (fixed-K padded tile indices, no retracing).
    measure=False drops the in-graph recall probe (and its dense gate-weight
    re-read) — the production configuration.

    Returns (logits (b, vocab_p), pages, new_masks (L, b, F), aux) with
    aux = (act (L, b, F), scores (L, b, F//tile'), density (L, b) fraction
    of FFN weight tiles read, n_active (L, b), n_miss (L, b) in-graph
    recall telemetry; zeros when measure=False; ffn_tiles as in
    ``decode_step_paged``).

    ``shards`` (static; the engine's mesh TP degree) switches the per-token
    packed tile lists to model-axis-local packing — see
    ``_ffn_decode_predicted``. 1 keeps the frozen single-device lowering."""
    ffn_stack = _ffn_stack(params, cfg, fast_kernels)

    def layer_fn(pl_i, li, x, kp, vp, fm, pred_l):
        x, kp, vp, act, scores, density, n_act, n_miss, ffn_tiles = \
            apply_block_decode_paged(
                pl_i, x, cfg, kp, vp, table, pos, layer=li,
                block_size=block_size, mask=fm, refresh=refresh,
                pred=pred_l, pred_kind=kind, pred_tile=tile, k_tiles=k_tiles,
                pred_measure=measure, pred_shards=shards,
                fast_kernels=fast_kernels, ffn_stack=ffn_stack)
        return x, kp, vp, (act, scores, density, n_act, n_miss, ffn_tiles)

    return sp.decode_step_core(params, pages, token, pos, cfg, ffn_masks,
                               refresh, layer_fn=layer_fn,
                               embed_fn=embed_tokens, logits_fn=logits_from,
                               extra_xs=(pred_params,))


def draft_gamma_paged(params, pages, table, token, pos0, wlen,
                      cfg: ModelConfig, *, gamma: int, block_size: int,
                      next_fn=None, fast_kernels: bool = False):
    """Draft γ tokens per slot in one jitted scan over the paged pool —
    the proposer half of speculative decoding, batched across slots with
    NO host round-trips.

    token: (b,) each slot's current (verified) token; pos0: (b,) its write
    position; wlen: (b,) the slot's verification window length W_s — draft
    step g writes position pos0+g only while g < W_s (out-of-window and
    idle-slot writes go to the scratch block). The scan runs γ+1 steps so
    the final proposal's own K/V is already in the draft cache when every
    draft is accepted (no hole to back-fill next round); the extra step's
    logits are discarded.

    next_fn(logits (b, vocab_p), g) -> (b,) int32 selects each step's
    proposal from the step's logits — the logits-out hook the serving
    engine uses to draft with per-slot sampling (sampling head + the
    shared key schedule). None keeps the frozen greedy argmax lowering.

    Returns (proposals (b, γ), pages)."""
    b = token.shape[0]
    masks = jnp.zeros((cfg.n_layers, b, cfg.d_ff), bool)
    refresh = jnp.ones((b,), bool)

    def step(carry, g):
        tok, pages = carry
        wl = (g < wlen).astype(wlen.dtype)  # 0/1: write-enable as W_s
        logits, pages, _, _ = verify_window_paged(
            params, pages, table, tok[:, None], pos0 + g, wl, cfg,
            masks, refresh, block_size=block_size,
            fast_kernels=fast_kernels)
        if next_fn is None:
            nxt = jnp.argmax(logits[:, 0, : cfg.vocab_size],
                             -1).astype(jnp.int32)
        else:
            nxt = next_fn(logits[:, 0], g)
        return (nxt, pages), nxt

    (_, pages), props = jax.lax.scan(
        step, (token, pages), jnp.arange(gamma + 1, dtype=wlen.dtype))
    return props[:gamma].T, pages


def prefill_paged(params, tokens, cfg: ModelConfig, pages, blocks,
                  *, block_size: int, true_len=None):
    """Prefill one request's prompt into freshly allocated pool blocks.

    tokens: (1, s); blocks: (nb,) with nb*block_size >= s. Returns
    (last-token logits (1, vocab_p), pages).

    true_len (traced scalar): real prompt length when `tokens` is
    zero-padded to a block multiple — the engine pads so compiles are keyed
    on block count (<= max_blocks_per_seq shapes), not raw prompt length.
    K/V written for pad positions is masked by `pos` until decode overwrites
    it in place."""
    li = None if true_len is None else true_len - 1
    logits, kv = forward(params, tokens, cfg, return_kv=True, last_index=li)
    k, v = kv  # (L, 1, s, kvp, hd)
    with jax.named_scope("kv_write"):
        kp = cm.paged_write_prefill(pages["k"], k[:, 0], blocks, block_size)
        vp = cm.paged_write_prefill(pages["v"], v[:, 0], blocks, block_size)
    return logits[:, -1], {"k": kp, "v": vp}
