"""Launchers: mesh, dry-run, train, serve."""

import os
import pathlib

# JAX's persistent compilation cache, at a fixed path inside the checkout:
# the path is part of the cache key, so it must not vary between runs.
COMPILE_CACHE_DIR = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def use_compile_cache() -> None:
    """Turns on JAX's persistent compilation cache for an entry point.

    ``JAX_COMPILATION_CACHE_DIR``, where set, is read by JAX itself and
    wins; otherwise the cache lives in ``.jax_cache/`` at the checkout root.
    """
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        import jax

        jax.config.update("jax_compilation_cache_dir", str(COMPILE_CACHE_DIR))
