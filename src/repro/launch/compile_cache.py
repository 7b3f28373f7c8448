"""Where JAX's persistent compilation cache lives for this checkout.

Each entry point's ``main()`` calls ``use_compile_cache()`` once, before its
first compile; library code and tests never do. A run then reads back what
earlier runs of the same checkout compiled, instead of compiling again.
"""
from __future__ import annotations

import os
from pathlib import Path

# <checkout>/.jax_cache — fixed, never a temporary, per-pid or timed path,
# so every run of this checkout looks in the same place (gitignored)
DEFAULT_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def use_compile_cache() -> str:
    """Return the persistent-cache directory this process compiles into.
    ``JAX_COMPILATION_CACHE_DIR``, when set, wins: JAX reads it by itself
    and nothing is set here. Otherwise the cache goes to ``DEFAULT_DIR``."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
