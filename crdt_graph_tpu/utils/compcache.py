"""Persistent XLA compilation cache.

The merge kernel takes minutes to compile for a TPU, and the serving
engine's jit cache is keyed by bucketed capacity (codec/packed.py) — so
without a persistent cache the first request at each power-of-two bucket
pays that again after every process restart.

Where the cache lives is decided from outside, never by a temp name:

- ``JAX_COMPILATION_CACHE_DIR`` set: JAX reads it itself, and nothing
  here sets another directory;
- unset: one fixed path inside the checkout, ``<checkout>/.jax_cache``
  (git-ignored).  The path is part of the cache key, so it must not
  move between runs.

Call :func:`enable` before the first jit compilation (every entry point:
chip_smoke.py, bench.py, ``python -m crdt_graph_tpu.bench``,
``.service``, ``.cluster``).  Idempotent.
"""
from __future__ import annotations

import os

DEFAULT_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax_cache")


def enable() -> str:
    """Enable the persistent compilation cache; returns the directory."""
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = DEFAULT_DIR
        os.makedirs(path, exist_ok=True)
        jax.config.update("jax_compilation_cache_dir", path)
    # cache every compilation that takes noticeable time (the default
    # threshold of 1 s would skip the small per-bucket engine kernels)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.1)
    return path
