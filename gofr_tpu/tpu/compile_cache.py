"""Where compiled executables persist between processes.

A cold start compiles every bucket and ladder rung (minutes at 7B
geometry); JAX's persistent compilation cache turns a restart on the same
checkout into cache reads. The directory is part of the cache key, so it
has to be the same path every run: either the one the deployment names in
``JAX_COMPILATION_CACHE_DIR`` or a fixed directory beside the package —
never a temp name, pid or timestamp. AOT ``.lower().compile()`` (the
executor's warmup ladder) reads and writes the same cache as ``jit``.
"""

from __future__ import annotations

import os

__all__ = ["configure_compile_cache"]

_CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def configure_compile_cache() -> str:
    """Point JAX's persistent compilation cache at its one directory and
    return it. Call before the first compile: JAX decides once per
    process, at that compile, whether a cache is in use.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set JAX has already read it,
    and nothing here sets another. Where it is unset the cache lives at
    ``<checkout>/.jax_cache`` (git-ignored)."""
    from_env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if from_env:
        return from_env
    import jax

    path = os.path.join(_CHECKOUT, ".jax_cache")
    if jax.config.jax_compilation_cache_dir != path:
        jax.config.update("jax_compilation_cache_dir", path)
    return path
