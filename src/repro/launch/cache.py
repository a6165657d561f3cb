"""JAX's persistent compilation cache, placed from outside the program.

`enable_compile_cache()` is the one place the repository chooses the
cache directory. Where `JAX_COMPILATION_CACHE_DIR` is set, JAX already
reads it and nothing else is set; otherwise the cache goes to the fixed
directory `<checkout>/.jax_cache` (listed in `.gitignore`), so a later run
from the same checkout finds what an earlier one compiled.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

DEFAULT_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent cache on and return its directory."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(DEFAULT_DIR)
        jax.config.update("jax_compilation_cache_dir", path)
    return path
