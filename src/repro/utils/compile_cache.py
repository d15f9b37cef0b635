"""JAX's persistent compilation cache for the repo's entry points.

Profiling jits every unique op signature separately, so a cold run
spends much of its time compiling; the persistent cache lets the next
process load those executables instead.  Entry points (``chip_smoke.py``,
examples, benchmarks) call `enable_compile_cache` once before any jit;
library code and tests never do.

The cache lives where ``JAX_COMPILATION_CACHE_DIR`` says when it is set,
and otherwise at ``<repo>/.jax_cache`` — a fixed path, because the path
is part of what makes a later process find the entries again.
"""
from __future__ import annotations

import os

import jax

REPO_ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__),
                                         "..", "..", ".."))
DEFAULT_CACHE_DIR = os.path.join(REPO_ROOT, ".jax_cache")


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or DEFAULT_CACHE_DIR
    jax.config.update("jax_compilation_cache_dir", path)
    # Per-op programs compile in well under jax's 1 s default threshold,
    # and they are most of what a profiling run compiles.
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return path
