"""Persistent XLA compile cache for the entry scripts.

Called by ``chip_smoke.py`` (``benchmarks/`` keeps its own) — never at import, and never
by the tests: on the CPU backend a cached executable reloaded in a fresh
process can fail its target-feature check and yield zero-filled outputs
(``tests/conftest.py``).
"""

import os

CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))),
    ".jax_cache")


def use_compile_cache() -> str:
    """Turn the cache on and return its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set JAX reads it itself, and no
    directory is set in code.  Otherwise the cache lives at a FIXED path
    under the checkout: the path is part of the cache key, so one derived
    from ``tempfile``, a pid or the time would never hit."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax
    jax.config.update("jax_compilation_cache_dir", CACHE_DIR)
    return CACHE_DIR
