"""Where the persistent XLA compilation cache lives.

Every entry point that compiles for the chip (``chip_smoke.py``, the
``examples/``) calls :func:`enable_compile_cache` once,
before its first compile. The directory is decided OUTSIDE the program
whenever the caller cares: where ``JAX_COMPILATION_CACHE_DIR`` is set, jax
itself reads it into ``jax_compilation_cache_dir`` and nothing here sets
another; where it is unset the cache is ``<checkout>/.jax_cache`` — a
fixed, git-ignored path, because the path is part of what makes a later
run find the entries again. ``import paddle_tpu`` touches none of this.
"""

from __future__ import annotations

import os

__all__ = ["enable_compile_cache"]

_CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on and return its directory
    (``JAX_COMPILATION_CACHE_DIR`` if set, else ``<checkout>/.jax_cache``).
    A directory that cannot be created is an error: on the chip a cold
    compile of everything is minutes, so a cache that silently is not
    there is a fault, not a degradation."""
    import jax
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = os.path.join(_CHECKOUT, ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", path)
    os.makedirs(path, exist_ok=True)
    # cache fast compiles too: the win is a warm restart of EVERY program
    # an engine builds, not only dedup of the slow ones
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.5)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return path
