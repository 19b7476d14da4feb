"""JAX's persistent compilation cache for the entry points.

A fresh process (and every call on a freshly provisioned chip machine)
otherwise recompiles the serving round, the trunk and every kernel.
Entry points call ``enable_compile_cache()`` from their ``main()``;
nothing enables it at import.

The cache directory is part of each entry's key, so it must not move
between runs: ``JAX_COMPILATION_CACHE_DIR`` when set (JAX reads it
itself, and this helper leaves it alone), else one fixed path inside
the checkout, ``<repo>/.jax_cache`` (gitignored), found from this
file's location rather than the working directory.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

REPO_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent cache on; returns the directory in use.

    The minimum compile time for caching drops to 0 so the Pallas
    kernels, which compile in well under the default 1 s, are kept too.
    """
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", str(REPO_CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    return jax.config.jax_compilation_cache_dir
