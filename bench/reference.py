"""Plain OPT in float32, and its control one precision below.

Follows the published OPT decoder (pre-LN, learned positions, ReLU MLP,
tied head) with the configuration file's sizes, reading the benchmark's
weights (``bench/weights.py``) and importing nothing of the program. Every
matmul runs at ``Precision.HIGHEST``: a float32 matmul on a TPU otherwise
runs one bfloat16 pass. One layer at a time goes through a ``lax.scan``,
upcast to float32 inside the body, so the float32 copy never exists for
the whole stack.

The control computes the same forward with every matmul operand rounded to
float8 (e4m3, per-row scales for activations, per-output-channel scales
for weights): the step below the bfloat16 the cells serve. It is read
only by the calibration (``bench/calibrate.py``), never by a timed run.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
F8_MAX = 448.0  # largest finite float8_e4m3fn


def _fp8(x, axis):
    """Round ``x`` to float8 e4m3 with one scale per slice along ``axis``."""
    s = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / F8_MAX
    s = jnp.where(s > 0, s, 1.0)
    return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def _mm(spec, a, b, control, a_axis, b_axis):
    if control:
        a, b = _fp8(a, a_axis), _fp8(b, b_axis)
    return jnp.einsum(spec, a, b, precision=HIGHEST)


def _ln(x, p, eps):
    mu = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), -1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + eps) * p["scale"] + p["bias"]


def _layer(x, lw, *, eps, control):
    lw = jax.tree.map(lambda a: a.astype(jnp.float32), lw)
    S = x.shape[0]
    H, hd = lw["attn"]["wq"].shape[1:]
    h = _ln(x, lw["ln1"], eps)
    # x (S, d) @ w (d, H, hd): activations scale per row, weights per (h, e)
    q = _mm("sd,dhe->she", h, lw["attn"]["wq"], control, -1, 0)
    k = _mm("sd,dhe->she", h, lw["attn"]["wk"], control, -1, 0)
    v = _mm("sd,dhe->she", h, lw["attn"]["wv"], control, -1, 0)
    s = _mm("she,the->hst", q * (hd ** -0.5), k, control, -1, -1)
    causal = jnp.arange(S)[:, None] >= jnp.arange(S)[None, :]
    s = jnp.where(causal[None], s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    o = _mm("hst,the->she", p, v, control, -1, 0)
    x = x + _mm("she,hed->sd", o, lw["attn"]["wo"], control, (-2, -1),
                (0, 1))
    h = _ln(x, lw["ln2"], eps)
    f = jax.nn.relu(_mm("sd,df->sf", h, lw["ffn"]["wu"], control, -1, 0))
    return x + _mm("sf,fd->sd", f, lw["ffn"]["wd"], control, -1, 0)


@functools.partial(jax.jit, static_argnames=("vocab", "eps", "control"))
def logits_at(w, tokens, rows, *, vocab: int, eps: float, control: bool):
    """Logits over the real vocabulary at positions ``rows`` of ``tokens``.

    tokens: (S,) int32, zero-padded past the sequence (causality keeps the
    real positions exact); rows: (R,) int32. Returns (R, vocab) float32.
    """
    S = tokens.shape[0]
    emb = w["embed"][:vocab].astype(jnp.float32)
    x = emb[tokens] + w["pos_embed"][:S].astype(jnp.float32)
    x, _ = jax.lax.scan(
        lambda x, lw: (_layer(x, lw, eps=eps, control=control), None),
        x, w["layers"])
    fn = jax.tree.map(lambda a: a.astype(jnp.float32), w["final_norm"])
    h = _ln(x[rows], fn, eps)
    return _mm("rd,vd->rv", h, emb, control, -1, -1)


def gaps(w, mc: dict, prompt, served, *, pad_to: int, control=False):
    """For each served token, the reference's best logit minus the logit of
    the token the run picked at that position; with ``control`` also the
    same gap for the token the control puts first. Returns
    (served_gaps, control_gaps or None), float64 arrays."""
    seq = np.concatenate([np.asarray(prompt), np.asarray(served[:-1])])
    if len(seq) > pad_to:
        raise ValueError(f"sequence of {len(seq)} exceeds pad_to={pad_to}")
    tokens = np.zeros(pad_to, np.int32)
    tokens[:len(seq)] = seq
    n = len(served)
    rows = np.zeros(pad_to, np.int32)  # fixed shape: one compile per size
    rows[:n] = len(prompt) - 1 + np.arange(n)
    eps = mc["assumed"]["layer_norm_eps"]
    kw = dict(vocab=mc["vocab_size"], eps=eps)
    ref = logits_at(w, jnp.asarray(tokens), jnp.asarray(rows), control=False,
                    **kw)
    best = jnp.max(ref, -1)
    picked = jnp.take_along_axis(ref, jnp.asarray(
        np.pad(np.asarray(served, np.int32), (0, pad_to - n)))[:, None], 1)
    served_gaps = np.asarray(best - picked[:, 0], np.float64)[:n]
    if not control:
        return served_gaps, None
    low = logits_at(w, jnp.asarray(tokens), jnp.asarray(rows), control=True,
                    **kw)
    top = jnp.argmax(low, -1)
    ctrl = best - jnp.take_along_axis(ref, top[:, None], 1)[:, 0]
    return served_gaps, np.asarray(ctrl, np.float64)[:n]
