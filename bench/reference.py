"""The plain float32 reference, and its control one precision below.

Each architecture's forward pass lives in its module,
``bench/arch/<model_type>.py``; this file holds what they share. Every
matmul runs at ``Precision.HIGHEST``: a float32 matmul on a TPU otherwise
runs one bfloat16 pass.

The control computes the same forward with every matmul operand rounded to
float8 (e4m3, per-row scales for activations, per-output-channel scales
for weights): the step below the bfloat16 the cells serve. It is read
only by the calibration (``bench/calibrate.py``), never by a timed run.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from bench import arch

HIGHEST = jax.lax.Precision.HIGHEST
F8_MAX = 448.0  # largest finite float8_e4m3fn


def fp8(x, axis):
    """Round ``x`` to float8 e4m3 with one scale per slice along ``axis``."""
    s = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / F8_MAX
    s = jnp.where(s > 0, s, 1.0)
    return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def mm(spec, a, b, control, a_axis, b_axis):
    """``einsum(spec, a, b)`` at HIGHEST; with ``control`` on float8
    operands, scaled along ``a_axis`` and ``b_axis``."""
    if control:
        a, b = fp8(a, a_axis), fp8(b, b_axis)
    return jnp.einsum(spec, a, b, precision=HIGHEST)


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
    logits_at = arch.load(mc).logits_at
    ref = logits_at(w, jnp.asarray(tokens), jnp.asarray(rows), mc=mc,
                    control=False)
    best = jnp.max(ref, -1)
    picked = jnp.take_along_axis(ref, jnp.asarray(
        np.pad(np.asarray(served, np.int32), (0, pad_to - n)))[:, None], 1)
    served_gaps = np.asarray(best - picked[:, 0], np.float64)[:n]
    if not control:
        return served_gaps, None
    low = logits_at(w, jnp.asarray(tokens), jnp.asarray(rows), mc=mc,
                    control=True)
    top = jnp.argmax(low, -1)
    ctrl = best - jnp.take_along_axis(ref, top[:, None], 1)[:, 0]
    return served_gaps, np.asarray(ctrl, np.float64)[:n]
