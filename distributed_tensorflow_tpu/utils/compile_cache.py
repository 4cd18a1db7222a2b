"""Persistent XLA compile cache at a place that can be chosen from outside.

Cold compiles are the larger part of a short accelerator run (a GPT-2-small
train step is ~15 s, each Pallas kernel 1-2 s), and the cache directory is
part of the cache key, so a directory that moves between runs never hits.
One rule for every entry point (``chip_smoke.py``, ``bench.py``, the
``examples/`` and the root scripts), applied before first backend use:

* ``JAX_COMPILATION_CACHE_DIR`` set: JAX reads it itself — nothing is set
  in code, so whoever placed the cache keeps control of it;
* otherwise: ``<checkout>/.jax_cache`` — a fixed path derived from this
  package's own location (never the working directory, a tempfile, a pid
  or a clock), listed in ``.gitignore``.
"""
from __future__ import annotations

import os

__all__ = ["DEFAULT_CACHE_DIR", "enable_compile_cache"]

DEFAULT_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax_cache")


def enable_compile_cache() -> str:
    """Turn the persistent compile cache on; returns the directory in use.

    Also lowers ``jax_persistent_cache_min_compile_time_secs`` to 0 so the
    1-2 s kernels are cached too (JAX's default skips anything under 1 s).
    """
    import jax
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", DEFAULT_CACHE_DIR)
    return DEFAULT_CACHE_DIR
