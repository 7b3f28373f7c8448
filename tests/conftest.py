"""Shared test configuration.

When the real `hypothesis` package is unavailable (it ships via the
``repro[test]`` extra; CI installs it), install a minimal deterministic
stand-in so the property-test modules still collect and run a reduced,
seeded example sweep instead of erroring at import time. The stub covers
exactly the API surface these tests use: ``given``, ``settings``, and the
``integers`` / ``floats`` / ``sampled_from`` / ``booleans`` strategies.
"""
from __future__ import annotations

import importlib.util
import os

# Exactness tests compare f32 streams across differently shaped executables
# (a W = 1 decode step against a W = γ+1 verify window). XLA:CPU's
# multi-threaded Eigen contraction splits a dot's reduction across threads
# by shape, so the same row of a (2, K) and a (6, K) product can round
# differently in the last ulp. Single-threaded Eigen takes that split out
# of the summation order. Set before any test builds a backend.
os.environ["XLA_FLAGS"] = " ".join(filter(None, [
    os.environ.get("XLA_FLAGS", ""), "--xla_cpu_multi_thread_eigen=false"]))

if importlib.util.find_spec("hypothesis") is None:  # pragma: no cover - CI has it
    import functools
    import inspect
    import random
    import sys
    import types
    import zlib

    class _Strategy:
        def __init__(self, draw):
            self._draw = draw

        def example_at(self, rng, i):
            return self._draw(rng, i)

    def integers(min_value, max_value):
        return _Strategy(lambda rng, i: min_value if i == 0 else
                         max_value if i == 1 else
                         rng.randint(min_value, max_value))

    def floats(min_value, max_value):
        return _Strategy(lambda rng, i: float(min_value) if i == 0 else
                         float(max_value) if i == 1 else
                         rng.uniform(min_value, max_value))

    def sampled_from(elements):
        elements = list(elements)
        return _Strategy(lambda rng, i: elements[i] if i < len(elements)
                         else rng.choice(elements))

    def booleans():
        return sampled_from([False, True])

    def given(*arg_strategies, **kw_strategies):
        def deco(fn):
            names = list(inspect.signature(fn).parameters)
            strategies = dict(zip(names, arg_strategies))
            strategies.update(kw_strategies)

            @functools.wraps(fn)
            def wrapper():
                n = getattr(wrapper, "_max_examples", 10)
                rng = random.Random(zlib.crc32(fn.__name__.encode()))
                for i in range(n):
                    kwargs = {k: s.example_at(rng, i)
                              for k, s in strategies.items()}
                    try:
                        fn(**kwargs)
                    except Exception as e:
                        raise AssertionError(
                            f"falsifying example (stub hypothesis): "
                            f"{kwargs!r}") from e

            # hide the original parameters from pytest's fixture resolution
            wrapper.__signature__ = inspect.Signature()
            return wrapper
        return deco

    def settings(max_examples=10, **_ignored):
        def deco(fn):
            fn._max_examples = max_examples
            return fn
        return deco

    st_mod = types.ModuleType("hypothesis.strategies")
    st_mod.integers = integers
    st_mod.floats = floats
    st_mod.sampled_from = sampled_from
    st_mod.booleans = booleans

    hyp_mod = types.ModuleType("hypothesis")
    hyp_mod.given = given
    hyp_mod.settings = settings
    hyp_mod.strategies = st_mod
    hyp_mod.__stub__ = True

    sys.modules["hypothesis"] = hyp_mod
    sys.modules["hypothesis.strategies"] = st_mod
