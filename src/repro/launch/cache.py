"""Where JAX keeps its persistent compilation cache.

Every entry point's ``main`` calls ``use_compile_cache()`` before it
compiles anything; nothing here runs on import. ``JAX_COMPILATION_CACHE_DIR``
stays in charge when it is set (JAX reads it itself and this module sets
no other directory). Otherwise the cache lives at one fixed path inside
the checkout, ``<repo>/.jax_cache`` (gitignored): never a temp name, pid or
time, so a second run from the same checkout finds what the first wrote.
"""

from __future__ import annotations

import os

import jax

REPO_ROOT = os.path.abspath(
    os.path.join(os.path.dirname(__file__), "..", "..", ".."))
DEFAULT_DIR = os.path.join(REPO_ROOT, ".jax_cache")
ENV = "JAX_COMPILATION_CACHE_DIR"


def use_compile_cache() -> str:
    """Place the persistent compilation cache; returns its directory."""
    if os.environ.get(ENV):
        return os.environ[ENV]
    jax.config.update("jax_compilation_cache_dir", DEFAULT_DIR)
    return DEFAULT_DIR
