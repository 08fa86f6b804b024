"""Persistent XLA compile cache, turned on by process entry points.

A cold 32-layer program is mostly compile, so every process that owns a
device (the inference server, chip_smoke.py's children, the
`run:` scripts of the training examples) calls `enable()` before its
first compile.  Never called at import or from a library constructor:
the CPU tests stay uncached.

The cache directory is part of the cache key, so it must not move:
`JAX_COMPILATION_CACHE_DIR`, when set, is JAX's own and nothing here
sets another; otherwise the directory is `<checkout>/.jax_cache`, worked
out from this package's location.
"""
from __future__ import annotations

import contextlib
import os

_CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
DEFAULT_DIR = os.path.join(_CHECKOUT, '.jax_cache')


def enable() -> str:
    """Point JAX at the persistent compile cache; returns its path."""
    env_dir = os.environ.get('JAX_COMPILATION_CACHE_DIR')
    if env_dir:
        return env_dir          # JAX reads the variable itself
    import jax
    jax.config.update('jax_compilation_cache_dir', DEFAULT_DIR)
    return DEFAULT_DIR


@contextlib.contextmanager
def bypassed():
    """Compile inside this block without the persistent cache.

    For executables with pinned, non-default output layouts (the decode
    engine's AOT layout pass).  On the installation there is (jax 0.9.0,
    libtpu 0.0.34) such an executable, loaded back from the cache, hands
    out its results in the default layout while still reporting the
    pinned one, and the next executable refuses them: the second start
    of a 7B server on a warm cache died at its first decode (chip run,
    PR 22).  JAX decides once per process whether the cache is in use,
    so the switch is process-wide and needs `reset_cache()`: a compile
    on another thread meanwhile misses the cache, which costs time only.
    """
    import jax
    from jax.experimental.compilation_cache import compilation_cache
    was_on = jax.config.jax_enable_compilation_cache
    jax.config.update('jax_enable_compilation_cache', False)
    compilation_cache.reset_cache()
    try:
        yield
    finally:
        jax.config.update('jax_enable_compilation_cache', was_on)
        compilation_cache.reset_cache()
