"""JAX's persistent compilation cache, kept where the next process finds it.

A program compiled at full width takes seconds to a minute; with the cache
on, a later process that compiles the same program reads it back instead.
The directory is part of what makes a hit possible, so it is fixed: the
``JAX_COMPILATION_CACHE_DIR`` environment variable where it is set (JAX
reads it itself), otherwise ``<checkout>/.jax_cache``, derived from this
package's location and never from the working directory, a temporary name,
a pid or the time.
"""
from __future__ import annotations

import os
from pathlib import Path
from typing import Optional

import jax

#: ``<checkout>/.jax_cache`` (this file is ``<checkout>/src/repro/...``)
CACHE_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def enable_compile_cache() -> Optional[str]:
    """Turn the persistent compilation cache on for an accelerator run;
    call before the first compile. CPU runs (the tests among them) keep no
    cache. Returns the directory in use, or None when the cache stays off."""
    if jax.default_backend() == "cpu":
        return None
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    return str(CACHE_DIR)
