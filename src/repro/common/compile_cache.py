"""JAX's persistent compilation cache for the entry points.

Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and
nothing is changed.  Otherwise the cache goes to one fixed directory in
the checkout, ``<checkout>/.jax_cache``: the directory is part of the
cache key, so a path that moved between runs (a temp dir, a pid, a
time) would never hit.  Call before the first compile.

Either way the program's metadata (op names with their
``repro.trainer.tracing`` scopes, source lines) is part of the key: an
executable loaded from the cache carries the metadata of the program
that compiled it, and a profile must name each op by the scopes of the
code that runs, not of another version with the same computation.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

CHECKOUT = Path(__file__).resolve().parents[3]


def enable_compile_cache() -> str:
    jax.config.update("jax_compilation_cache_include_metadata_in_key", True)
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if path:
        return path
    path = str(CHECKOUT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
