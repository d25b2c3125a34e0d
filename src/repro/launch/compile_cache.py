"""Where JAX keeps its persistent compilation cache.

Entry points (``launch/train.py``, ``launch/serve.py``, ``chip_smoke.py``)
call :func:`enable_compile_cache` from their ``main`` — never at import
time, so tests and library users keep JAX's own default (no cache).
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

# fixed, inside the checkout (gitignored): a later process finds what an
# earlier one compiled only if the directory does not move
REPO_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on the persistent compilation cache; returns its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and
    nothing is set here; otherwise the cache goes to ``<repo>/.jax_cache``.

    Cache keys include the program's metadata (named scopes, files, lines).
    By default JAX strips it, so an executable loaded from the cache keeps
    the op names of whichever build first compiled the same HLO, and a
    profile then attributes device time to that build's scopes.
    """
    jax.config.update("jax_compilation_cache_include_metadata_in_key", True)
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(REPO_CACHE_DIR))
    return str(REPO_CACHE_DIR)
