"""Where the program keeps JAX's persistent compilation cache.

One rule, one function, called once by every entry point that compiles
(``chip_smoke.py``, ``bench.py``, the examples, ``serve_fleet.run_replica``
and ``launch.run``):

- ``JAX_COMPILATION_CACHE_DIR`` set: JAX reads it itself and the program
  names no directory in code — the machine decides where the cache lives
  and whether it survives the process.
- not set: ``<checkout>/.jax_cache`` (git-ignored). A fixed path, never a
  temporary, pid- or time-derived one: the directory is part of the cache
  key's context, so a cache that moves never hits.

A directory an embedding program already configured (the test suite's
``.jax_test_cache``, tests/conftest.py) is left alone.
"""

from __future__ import annotations

import os

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"


def checkout_cache_dir() -> str:
    """``<checkout>/.jax_cache`` — the directory above the package."""
    package = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    return os.path.join(os.path.dirname(package), ".jax_cache")


def configure_compile_cache() -> str:
    """Apply the rule above; returns the directory in use."""
    from_env = os.environ.get(ENV_VAR)
    if from_env:
        return from_env
    import jax

    configured = jax.config.jax_compilation_cache_dir
    if configured:
        return configured
    path = checkout_cache_dir()
    jax.config.update("jax_compilation_cache_dir", path)
    return path
