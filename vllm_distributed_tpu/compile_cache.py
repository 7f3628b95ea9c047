"""Where compiled programs are kept between processes.

One rule for the worker, the AOT artifact cache, the benchmark and the
tests: if ``JAX_COMPILATION_CACHE_DIR`` is set, JAX's own handling of
it stands and this module configures nothing; otherwise the cache is
``<checkout>/.jax_cache``.  The path must be stable across processes —
a directory that moves between runs never hits — so nothing here
creates a temporary one.  The ``jax.export`` artifacts
(worker/aot_cache.py) live in ``aot/`` under whichever applies.
"""

from __future__ import annotations

import os

_ENV = "JAX_COMPILATION_CACHE_DIR"
_CHECKOUT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def compile_cache_dir() -> str:
    return os.environ.get(_ENV) or os.path.join(_CHECKOUT, ".jax_cache")


def enable_compile_cache() -> str:
    """Point JAX's persistent cache at ``compile_cache_dir()``; call
    before the first compile of the process.  Returns the directory."""
    path = compile_cache_dir()
    if not os.environ.get(_ENV):
        import jax

        jax.config.update("jax_compilation_cache_dir", path)
    return path
