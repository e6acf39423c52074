"""Where JAX's persistent compilation cache lives (no jax at import).

One rule for every entry point (``hvd.init()`` on an accelerator, hence
``chip_smoke.py``, ``benchmark/`` and the workers ``torovodrun`` starts):
where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and the
program sets nothing; where it is not, the cache goes to ``.jax_cache/``
beside the package (git-ignored).  The path is part of no key but a
directory that moves never hits, so it is never built from a temporary
name, a pid or the time.
"""

from __future__ import annotations

import os

ENV = "JAX_COMPILATION_CACHE_DIR"
_FIXED = os.path.join(os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))), ".jax_cache")


def cache_dir() -> str:
    """The directory every process of this program resolves to."""
    return os.environ.get(ENV) or _FIXED


def enable() -> str:
    """Point JAX at :func:`cache_dir` and return it.

    A no-op where the environment already placed the cache.  Otherwise the
    cache's lazily-latched "is it used" verdict is reset, so a program
    that compiled something before ``hvd.init()`` still gets the cache for
    everything after.
    """
    import jax
    path = cache_dir()
    if jax.config.jax_compilation_cache_dir != path:
        jax.config.update("jax_compilation_cache_dir", path)
        from jax.experimental.compilation_cache import compilation_cache
        compilation_cache.reset_cache()
    return path
