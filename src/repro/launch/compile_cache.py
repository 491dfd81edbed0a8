"""Persistent XLA compilation cache for the command-line entry points.

Compiling the kernels and fixpoints is a large share of a cold run on the
chip, so `graph_run`, `serve_graph`, `benchmarks.run` and `chip_smoke.py`
keep JAX's persistent compilation cache on. Where it lives is decided
outside the program: JAX itself reads `JAX_COMPILATION_CACHE_DIR`, and
when that is set nothing here touches the cache configuration. Otherwise
the cache goes to the fixed `<repo>/.jax_cache` (git-ignored): the path
is part of each entry's key, so it never comes from a temporary name, a
pid or the time. Tests never call this.
"""
from __future__ import annotations

import os

import jax

REPO_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))), ".jax_cache")


def enable_compile_cache() -> str:
    """Turn the persistent cache on; returns the directory in use."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if path:
        return path
    jax.config.update("jax_compilation_cache_dir", REPO_CACHE_DIR)
    return REPO_CACHE_DIR
