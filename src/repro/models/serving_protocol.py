"""Family-agnostic paged-serving protocol.

Two things live here, and both exist so the serving engine never has to
know which model family it is driving:

1. **Declared capabilities** (``ServingCaps``): every family registered in
   models/registry.py declares which paged-serving entry points it
   implements, as a set of capability names from ``CAP_FUNCS``. Declaring a
   capability is validated EARLY (at registration: the named module
   functions must exist), and the engine checks requirements with ONE
   uniform error message (``ServingCaps.require``) instead of scattered
   ``hasattr(fam, "model_decode_paged")`` probes — an unsupported-family
   error always names the missing capability and what the family does
   declare.

2. **The shared paged-decode skeleton**: every family's paged serving entry
   points are the same sandwich — embed → per-layer scan carrying the paged
   K/V pool (attention through the block table, then the family's FFN
   dispatch) → γ-window mask refresh → final norm + logits head. The
   transformer and MoE families previously each spelled this out;
   ``decode_step_core`` / ``window_step_core`` hold it once, parameterized
   by the family's per-layer block function (``layer_fn``) and its
   embed/logits callables. The cores are pure structural plumbing: a family
   delegating to them emits the SAME jaxpr as the hand-written loop it
   replaces, so the dense family's bit-frozen serving lowerings (bf16
   exactness pins) are unchanged.

Family hooks with defaults (resolved here so the engine itself contains no
``hasattr``/``getattr`` family probes):

* ``prompt_token_offset(cfg) -> int`` — extra non-text positions a family
  prepends to the prompt (vision patches for vlm); the legacy ServeEngine
  offsets decode positions by it. Default 0.
"""
from __future__ import annotations

from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.models import common as cm
from repro.sharding import rules

PyTree = Any

# capability name -> module functions a family must define to declare it.
# The engine requires:
#   paged_decode    — any ContinuousBatchingEngine at all
#   chunked_prefill — prefill_chunk > 0 (and prefix_cache, which needs it)
#   spec_verify     — speculative mode's TARGET family
#   spec_draft      — speculative mode's DRAFT family
#   predictor       — predictor serving mode
CAP_FUNCS: Dict[str, Tuple[str, ...]] = {
    "paged_decode": ("init_paged_cache", "model_prefill_paged",
                     "model_decode_paged"),
    "chunked_prefill": ("model_prefill_chunk_paged",),
    "spec_verify": ("model_verify_window_paged",),
    "spec_draft": ("model_draft_gamma_paged",),
    "predictor": ("model_decode_paged_predicted",),
}


class ServingCaps(frozenset):
    """A family's declared paged-serving capability set (names from
    ``CAP_FUNCS``). Frozen so it can be declared once at registration and
    shared; ``require`` is the engine's single capability gate."""

    def require(self, cap: str, family: str) -> None:
        """Raise the uniform unsupported-capability ValueError unless this
        family declared ``cap``."""
        if cap not in CAP_FUNCS:
            raise KeyError(f"unknown serving capability {cap!r} "
                           f"(known: {sorted(CAP_FUNCS)})")
        if cap not in self:
            declared = ", ".join(sorted(self)) if self else "none"
            raise ValueError(
                f"family {family!r} does not support the {cap!r} serving "
                f"capability (declared capabilities: {declared})")


def validate_caps(name: str, module, caps: ServingCaps) -> None:
    """Early registration-time check: every declared capability's functions
    must exist on the family module — a typo'd declaration fails at
    register_family(), not at first serve."""
    for cap in caps:
        if cap not in CAP_FUNCS:
            raise ValueError(f"family {name!r} declares unknown serving "
                             f"capability {cap!r} (known: "
                             f"{sorted(CAP_FUNCS)})")
        missing = [f for f in CAP_FUNCS[cap] if not hasattr(module, f)]
        if missing:
            raise ValueError(
                f"family {name!r} declares capability {cap!r} but is "
                f"missing {missing}")


def prompt_token_offset(fam, cfg) -> int:
    """The family's extra prompt-position offset (default 0). Families with
    non-text prefix positions (vlm vision patches) define
    ``prompt_token_offset(cfg)``; resolved here so engines stay free of
    per-family probes."""
    hook = getattr(fam, "prompt_token_offset", None)
    return 0 if hook is None else int(hook(cfg))


# ---------------------------------------------------------------------------
# shared paged-decode skeleton


def refresh_union_masks(ffn_masks, act, refresh):
    """γ-window mask update shared by every paged step: slots flagged
    ``refresh`` replace their mask row with this step's (union) activity,
    others keep the window's mask. Constrained d_ff-over-"model" for TP
    serving (identity without a mesh)."""
    return rules.constrain(
        jnp.where(refresh[None, :, None], act, ffn_masks),
        None, "dp", "model")


def no_ffn_tiles(b: int, n_layers: Optional[int] = None):
    """The decode aux's ``ffn_tiles`` where no fused FFN kernel ran: no
    tiles named by any of the ``b`` slots (b,) and none fetched (). With
    ``n_layers``, stacked on a leading layer axis like a whole step's."""
    lead = () if n_layers is None else (n_layers,)
    return (jnp.zeros(lead + (b,), jnp.int32), jnp.zeros(lead, jnp.int32))


def scan_layers_paged(params, pages, cfg, x, layer_fn: Callable,
                      extra_xs: Tuple = ()):
    """The per-layer paged scan: carry (x, k_pages, v_pages) through the
    stacked layers; each layer writes its K/V through the block table and
    returns its FFN telemetry as the scan's stacked ys.

    layer_fn(pl_i, li, x, k_pages, v_pages, ffn_mask, *extras)
        -> (x, k_pages, v_pages, aux_tuple)

    Returns ((x, k_pages, v_pages), aux) with every aux leaf stacked on a
    leading layer axis."""
    def body(carry, xs):
        x, kp, vp = carry
        pl_i, li, fm = xs[:3]
        x, kp, vp, aux = layer_fn(pl_i, li, x, kp, vp, fm, *xs[3:])
        return (x, kp, vp), aux

    xs = (params["layers"], jnp.arange(cfg.n_layers)) + extra_xs
    return jax.lax.scan(body, (x, pages["k"], pages["v"]), xs)


def decode_step_core(params, pages, token, pos, cfg, ffn_masks, refresh, *,
                     layer_fn: Callable, embed_fn: Callable,
                     logits_fn: Callable, extra_xs: Tuple = ()):
    """Generic single-token paged decode: embed → scan_layers_paged →
    mask refresh → final norm + logits. token/pos/refresh: (b,);
    ffn_masks: (L, b, F). layer_fn's aux tuple must lead with the (b, F)
    FFN activity (it feeds the mask refresh); the whole stacked aux tuple
    is returned untouched.

    Returns (logits (b, vocab_p), pages, new_masks (L, b, F), aux)."""
    params = cm.cast_params(params, cfg)
    x = embed_fn(params, token[:, None], cfg, pos[:, None])[:, 0]
    x = rules.constrain(x, "dp", None)

    (x, kp, vp), aux = scan_layers_paged(params, pages, cfg, x, layer_fn,
                                         (ffn_masks,) + extra_xs)
    new_masks = refresh_union_masks(ffn_masks, aux[0], refresh)

    x = cm.apply_norm(params["final_norm"], x[:, None], cfg)[:, 0]
    logits = logits_fn(params, x, cfg)
    return logits, {"k": kp, "v": vp}, new_masks, aux


def window_step_core(params, pages, tokens, pos0, wlen, cfg, ffn_masks,
                     refresh, *, layer_fn: Callable, embed_fn: Callable,
                     logits_fn: Callable):
    """Generic W-token paged window step (speculative verify / chunked
    prefill; W == 1 is a plain decode step). tokens: (b, W); pos0/wlen/
    refresh: (b,). layer_fn additionally receives the window's per-token
    write positions pos (b, W) and validity valid (b, W); its aux tuple must
    lead with the (b, F) window-union FFN activity.

    Returns (logits (b, W, vocab_p), pages, new_masks (L, b, F), aux)."""
    params = cm.cast_params(params, cfg)
    b, W = tokens.shape
    pos = pos0[:, None] + jnp.arange(W, dtype=pos0.dtype)[None, :]
    valid = jnp.arange(W)[None, :] < wlen[:, None]
    x = rules.constrain(embed_fn(params, tokens, cfg, pos), "dp", None, None)

    def wrapped(pl_i, li, x, kp, vp, fm):
        return layer_fn(pl_i, li, x, kp, vp, fm, pos, valid)

    (x, kp, vp), aux = scan_layers_paged(params, pages, cfg, x, wrapped,
                                         (ffn_masks,))
    new_masks = refresh_union_masks(ffn_masks, aux[0], refresh)

    x = cm.apply_norm(params["final_norm"], x, cfg)
    logits = logits_fn(params, x, cfg)
    return logits, {"k": kp, "v": vp}, new_masks, aux
