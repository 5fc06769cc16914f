"""JAX's persistent compile cache, placed for the entry points.

Every entry point (``chip_smoke.py``, ``launch.compress``,
``launch.serve``, ``benchmarks.run``) calls ``enable_compile_cache()``
before it compiles anything, so that a later run on the same machine
can read programs back from the cache instead of compiling them again.

Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and
this sets no other directory. Otherwise the cache lives in
``.jax_cache/`` at the root of the checkout: a fixed path derived from
this file's location, because the directory is part of what a later
run must find again (git ignores it).
"""

from __future__ import annotations

import os

import jax

_ENV = "JAX_COMPILATION_CACHE_DIR"

#: ``<checkout>/.jax_cache`` (this file is ``src/repro/launch/...``).
DEFAULT_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))), ".jax_cache")


def enable_compile_cache() -> str:
    """Turn on the persistent compile cache; returns its directory.

    JAX's default threshold stays: only programs that took a second or
    more to compile are written. The many small eager programs of the
    float path fall below it and are compiled again on every run.
    """
    path = os.environ.get(_ENV)
    if not path:
        path = DEFAULT_DIR
        jax.config.update("jax_compilation_cache_dir", path)
    return path
