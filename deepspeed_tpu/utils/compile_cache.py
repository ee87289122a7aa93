"""Placement of JAX's persistent compilation cache.

The cache directory is part of the cache key's world: a directory that
moves between runs never hits. So the rule is one of two fixed places —

- ``JAX_COMPILATION_CACHE_DIR`` set in the environment: jax reads it on
  its own; nothing here names a directory;
- unset: ``default_dir``, one fixed path inside the checkout
  (``<repo>/.jax_cache``, git-ignored). Never a temp dir, a pid or a time.

Entry points (``chip_smoke.py``, ``bench.py``, ``benchmark/run.py``,
``examples/_common.py``, ``tests/conftest.py``) call
:func:`enable_compile_cache` once, before their first compile.
"""

import os

_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

#: the one fixed in-checkout cache path used when the environment names none
DEFAULT_CACHE_DIR = os.path.join(_REPO_ROOT, ".jax_cache")


def enable_compile_cache(default_dir: str = DEFAULT_CACHE_DIR,
                         min_compile_secs: float = 1.0) -> str:
    """Turn the persistent compile cache on; returns the directory in use.

    ``min_compile_secs``: programs that compile faster than this are not
    written (jax's own default is 1 s; the CPU test suite lowers it — its
    wall clock is thousands of sub-second compiles)."""
    import jax
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir",
                          os.path.abspath(default_dir))
    jax.config.update("jax_persistent_cache_min_compile_time_secs",
                      float(min_compile_secs))
    return str(jax.config.jax_compilation_cache_dir)
