"""Properties of the serving sampling head (serving/sampling.py).

Hypothesis-driven invariants over filter_logits / sample_head — support
sizes, renormalization, the greedy special case — plus the host-side key
schedule contract (fingerprints independent of uid/admission order).
Logits are generated with DISTINCT values so top-k/top-p supports are
unambiguous (ties legitimately grow the support; that path is covered
explicitly at the end).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.serving import sampling as smp

V = 64


def _distinct_logits(seed: int, B: int = 3, scale: float = 0.37):
    """(B, V) f32 rows with all-distinct values."""
    rng = np.random.RandomState(seed)
    return jnp.asarray(
        rng.permutation(B * V).reshape(B, V).astype(np.float32) * scale)


def _keys(seed: int, B: int = 3):
    return jnp.stack([jnp.asarray(
        jax.random.fold_in(jax.random.PRNGKey(seed), i), jnp.uint32)
        for i in range(B)])


def _full(B, t=1.0, k=0, p=1.0):
    return (jnp.full((B,), t, jnp.float32), jnp.full((B,), k, jnp.int32),
            jnp.full((B,), p, jnp.float32))


# -- greedy branch -----------------------------------------------------------


@settings(max_examples=12, deadline=None)
@given(st.integers(0, 10_000))
def test_temperature_zero_is_exact_argmax(seed):
    logits = _distinct_logits(seed)
    t, k, p = _full(3, t=0.0, k=5, p=0.5)  # filters must not bind at T=0
    nxt, lp = smp.sample_head(logits, V, t, k, p, _keys(seed))
    want = jnp.argmax(logits, -1).astype(jnp.int32)
    np.testing.assert_array_equal(np.asarray(nxt), np.asarray(want))
    want_lp = jnp.take_along_axis(jax.nn.log_softmax(logits, -1),
                                  want[:, None], -1)[:, 0]
    np.testing.assert_array_equal(np.asarray(lp), np.asarray(want_lp))


@settings(max_examples=8, deadline=None)
@given(st.integers(0, 10_000))
def test_temperature_to_zero_recovers_argmax(seed):
    """T→0+ through the SAMPLING branch: the scaled distribution collapses
    onto the argmax, so categorical sampling returns it."""
    logits = _distinct_logits(seed)
    t, k, p = _full(3, t=1e-5)
    nxt, _ = smp.sample_head(logits, V, t, k, p, _keys(seed))
    np.testing.assert_array_equal(np.asarray(nxt),
                                  np.asarray(jnp.argmax(logits, -1)))


# -- support invariants ------------------------------------------------------


@settings(max_examples=12, deadline=None)
@given(st.integers(1, V), st.integers(0, 10_000))
def test_top_k_support(k, seed):
    logits = _distinct_logits(seed)
    t, kk, p = _full(3, t=1.0, k=k)
    filt = np.asarray(smp.filter_logits(logits, kk, p, t))
    for b in range(3):
        kept = np.flatnonzero(np.isfinite(filt[b]))
        assert len(kept) == min(k, V)
        # the kept set IS the k largest logits
        want = np.argsort(np.asarray(logits[b]))[-k:]
        assert set(kept.tolist()) == set(want.tolist())


@settings(max_examples=12, deadline=None)
@given(st.floats(0.05, 1.0), st.integers(0, 10_000))
def test_top_p_support_is_minimal_nucleus(p, seed):
    logits = _distinct_logits(seed)
    t, k, pp = _full(3, t=1.0, p=p)
    filt = np.asarray(smp.filter_logits(logits, k, pp, t))
    probs = np.asarray(jax.nn.softmax(logits, -1))
    for b in range(3):
        kept = np.flatnonzero(np.isfinite(filt[b]))
        assert len(kept) >= 1
        mass = probs[b, kept].sum()
        # the nucleus reaches p...
        assert mass >= p - 1e-5
        # ...and is minimal: dropping its least-probable member dips below
        if len(kept) > 1:
            assert mass - probs[b, kept].min() < p + 1e-5
        # and it is a prefix of the probability ordering
        want = np.argsort(probs[b])[-len(kept):]
        assert set(kept.tolist()) == set(want.tolist())


@settings(max_examples=10, deadline=None)
@given(st.integers(1, V), st.floats(0.1, 1.0), st.integers(0, 10_000),
       st.floats(0.2, 3.0))
def test_filtered_rows_renormalize(k, p, seed, temp):
    """log_softmax over the filtered row sums to 1 on its support, and the
    reported logprob of a sampled token matches that renormalized
    distribution (NOT the unfiltered one)."""
    logits = _distinct_logits(seed)
    t, kk, pp = _full(3, t=temp, k=k, p=p)
    filt = smp.filter_logits(logits, kk, pp, t)
    lsm = np.asarray(jax.nn.log_softmax(filt, -1))
    for b in range(3):
        kept = np.isfinite(np.asarray(filt[b]))
        np.testing.assert_allclose(np.exp(lsm[b][kept]).sum(), 1.0,
                                   rtol=1e-5)
    nxt, lp = smp.sample_head(logits, V, t, kk, pp, _keys(seed))
    for b in range(3):
        assert np.isfinite(np.asarray(filt[b])[int(nxt[b])])  # in support
        np.testing.assert_allclose(float(lp[b]), lsm[b][int(nxt[b])],
                                   rtol=1e-5)


@settings(max_examples=10, deadline=None)
@given(st.integers(1, 8), st.integers(0, 10_000))
def test_sampled_token_respects_joint_support(k, seed):
    """top-k AND top-p together: the sample lands in the intersection."""
    logits = _distinct_logits(seed)
    t, kk, pp = _full(3, t=1.3, k=k, p=0.7)
    filt = np.asarray(smp.filter_logits(logits, kk, pp, t))
    nxt, _ = smp.sample_head(logits, V, t, kk, pp, _keys(seed))
    for b in range(3):
        assert np.isfinite(filt[b][int(nxt[b])])


def test_ties_keep_the_argmax_reachable():
    """Tied boundary values all stay in the support (the support can only
    grow on ties — never lose the argmax)."""
    row = np.zeros((1, V), np.float32)
    row[0, :4] = 5.0  # four-way tie at the top
    t, k, p = _full(1, t=1.0, k=2)
    filt = np.asarray(smp.filter_logits(jnp.asarray(row), k, p, t))
    kept = np.flatnonzero(np.isfinite(filt[0]))
    assert set(kept.tolist()) == {0, 1, 2, 3}


# -- determinism / key schedule ----------------------------------------------


def test_same_key_same_sample_different_key_varies():
    logits = _distinct_logits(1)
    t, k, p = _full(3, t=1.0)
    keys = _keys(11)
    a, _ = smp.sample_head(logits, V, t, k, p, keys)
    b, _ = smp.sample_head(logits, V, t, k, p, keys)
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    draws = [np.asarray(smp.sample_head(logits, V, t, k, p, _keys(s))[0])
             for s in range(40)]
    assert len({tuple(d.tolist()) for d in draws}) > 1


def test_request_fingerprint_contract():
    """Fingerprint covers prompt + distribution params; excludes max_new
    and stop (stream-prefix stability), and python-hash salting never
    enters (blake2b)."""
    sp = smp.SamplingParams(temperature=0.8, top_k=10, top_p=0.9, seed=3)
    f = smp.request_fingerprint([1, 2, 3], sp)
    assert f == smp.request_fingerprint([1, 2, 3], sp)
    assert f != smp.request_fingerprint([1, 2, 4], sp)
    assert f != smp.request_fingerprint(
        [1, 2, 3], smp.SamplingParams(temperature=0.9, seed=3))
    # stop sequences and seed do NOT shift the fingerprint (seed enters
    # the key via PRNGKey(seed), not the hash)
    assert f == smp.request_fingerprint([1, 2, 3], smp.SamplingParams(
        temperature=0.8, top_k=10, top_p=0.9, seed=4, stop=((7,),)))
    k1 = smp.request_prng_key([1, 2, 3], sp)
    k2 = smp.request_prng_key([1, 2, 3], sp)
    np.testing.assert_array_equal(k1, k2)
    assert k1.shape == (2,) and k1.dtype == np.uint32
    # different seed -> different key, same fingerprint
    k3 = smp.request_prng_key([1, 2, 3], smp.SamplingParams(
        temperature=0.8, top_k=10, top_p=0.9, seed=4))
    assert not np.array_equal(k1, k3)


def test_sampling_params_validation():
    import pytest
    with pytest.raises(ValueError):
        smp.SamplingParams(temperature=-0.1)
    with pytest.raises(ValueError):
        smp.SamplingParams(top_k=-1)
    with pytest.raises(ValueError):
        smp.SamplingParams(top_p=0.0)
    with pytest.raises(ValueError):
        smp.SamplingParams(top_p=1.5)
    assert smp.SamplingParams().is_greedy
    assert not smp.SamplingParams(temperature=0.5).is_greedy
