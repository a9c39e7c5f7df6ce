"""Where JAX keeps its persistent compilation cache — set in this one place.

Entry points that compile for the chip (``chip_smoke.py``,
``benchmarks/run.py``) call :func:`use_compile_cache` before their first
compile.  ``JAX_COMPILATION_CACHE_DIR``, when set, wins: JAX reads it
itself and nothing is set here.  Otherwise the cache lives at the fixed
path ``<checkout>/.jax_cache`` (listed in ``.gitignore``).  The path is
part of each entry's key, so it never depends on a temp name, a pid or the
time: a second run from the same checkout finds what the first one wrote.
"""

from __future__ import annotations

import os

CHECKOUT = os.path.dirname(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
)


def use_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax

    path = os.path.join(CHECKOUT, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
